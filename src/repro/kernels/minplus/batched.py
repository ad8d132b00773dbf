"""Batched Pallas TPU kernel: one DP superstep for a request grid.

``core.leastcost.leastcost_jax_batched`` serves the online placer: B mapping
requests relax against ONE shared resource network.  One superstep is

    place:  P[b,v,k]  = min_{j<=k, prefix[b,k]-prefix[b,j] <= cap[v]} C[b,v,j]
    move:   C'[b,w,k] = min_{v, bw[v,w] >= breq_k[b,k]}  P[b,v,k] + lat[v,w]
    update: Cn = where(C' < C - EPS_IMPROVE, C', C)   (+ parent pointers)

The place step is O(B * n * K^2) and runs as the jnp mirror
(:func:`_place_batched_ref`).  The move and the update, O(B * n^2 * K), run
as ONE ``pallas_call`` with grid ``(batch, w_blocks, v_blocks)``:

- the network tiles (``lat``/``bw``) use index maps that IGNORE the batch
  coordinate, so they are the same VMEM-resident tiles for every request;
- a ``b_tile``-row batch block amortizes each network tile over ``b_tile``
  requests, walked by a ``fori_loop`` (the compiled body holds one request);
- the K columns of one request are one block (``K_pad`` = p_max+1 rounded up
  to 8), unrolled per column as mask/shift/min VPU ops on a (V, W) tile; the
  (V, W, K) move candidates never touch HBM;
- ``P`` enters v-major (B, n, K) so a column is a lane broadcast; the state
  is K-major (B, K, n) inside the call so each column's result is a row.

HBM traffic per superstep (fp32 words): O((B / b_tile) * n^2 + B * n * K *
n / w_tile).  Min-plus has no MXU path; everything runs on the VPU.

``batched_superstep_ref`` is the fused pure-jnp oracle used off-TPU and as
the CI cross-check: it mirrors ``core.leastcost._superstep``'s exact update
semantics (same tie-breaking, same EPS thresholds) bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.problem import BIG, EPS_CAP_F32, EPS_IMPROVE

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)

# Defaults: (8, 128)-aligned network tiles; b_tile=8 amortizes each lat/bw
# tile fetch over 8 requests (smaller batches use b_tile = B).
B_TILE = 8
V_TILE = 128
W_TILE = 128
K_ALIGN = 8  # K_pad = p_max + 1 rounded up to the sublane count

DEFAULT_TILES = (B_TILE, V_TILE, W_TILE)


def resolve_tiles(tiles, B: int) -> tuple[int, int, int]:
    """``tiles`` or the defaults, with ``b_tile`` capped at the batch size."""
    b_tile, v_tile, w_tile = tiles or DEFAULT_TILES
    return min(b_tile, B), v_tile, w_tile


def vmem_model_bytes(b_tile: int, v_tile: int, w_tile: int, k_pad: int) -> int:
    """fp32 VMEM live-set of one grid step, double-buffered: the v-major
    ``P``/``pj`` blocks (their K minor dim pads to 128 lanes), the network
    tiles, and six K-major state blocks (three in, three out)."""
    lanes = -(-k_pad // 128) * 128
    blocks = (2 * b_tile * v_tile * lanes      # P, pj
              + 2 * v_tile * w_tile            # lat, bw
              + 6 * b_tile * k_pad * w_tile)   # prev + new C / par_v / par_j
    return 4 * 2 * blocks


def _move_kernel(breq_ref, p_ref, pj_ref, lat_ref, bw_ref, c_prev_ref,
                 pv_prev_ref, pj_prev_ref, c_ref, pv_ref, pjo_ref):
    b_blk = pl.program_id(0)
    v_blk = pl.program_id(2)
    nv = pl.num_programs(2)
    BT, V, KP = p_ref.shape
    W = lat_ref.shape[1]

    @pl.when(v_blk == 0)
    def _init():
        c_ref[...] = jnp.full(c_ref.shape, BIG, jnp.float32)
        pv_ref[...] = jnp.zeros(pv_ref.shape, jnp.int32)
        pjo_ref[...] = jnp.zeros(pjo_ref.shape, jnp.int32)

    lat = lat_ref[...]  # (V, W) — shared across the batch dimension
    bw = bw_ref[...]
    v_iota = jax.lax.broadcasted_iota(jnp.int32, (V, W), 0)

    def one_request(bi, carry):
        row0 = (b_blk * BT + bi) * KP
        P = p_ref[bi]  # (V, KP)
        pj = pj_ref[bi]
        for k in range(KP):
            cand = jnp.where(bw >= breq_ref[row0 + k], P[:, k:k + 1] + lat,
                             BIG)
            cand = jnp.minimum(cand, BIG)  # BIG + lat must stay min-plus BIG
            best = jnp.min(cand, axis=0, keepdims=True)  # (1, W)
            # first v achieving the min (jnp.argmin's tie rule)
            arg = jnp.min(jnp.where(cand == best, v_iota, V), axis=0,
                          keepdims=True)
            # place-argmin at the winning v, one-hot (no dynamic gather)
            pjw = jnp.max(jnp.where(v_iota == arg, pj[:, k:k + 1], -1),
                          axis=0, keepdims=True)
            prev = c_ref[bi, k:k + 1, :]
            take = best < prev  # strict: earlier v-tile wins ties
            c_ref[bi, k:k + 1, :] = jnp.where(take, best, prev)
            pv_ref[bi, k:k + 1, :] = jnp.where(take, arg + v_blk * V,
                                               pv_ref[bi, k:k + 1, :])
            pjo_ref[bi, k:k + 1, :] = jnp.where(take, pjw,
                                                pjo_ref[bi, k:k + 1, :])
        return carry

    jax.lax.fori_loop(0, BT, one_request, 0)

    @pl.when(v_blk == nv - 1)
    def _final():  # monotone EPS_IMPROVE update vs the previous superstep
        cmv = c_ref[...]
        cprev = c_prev_ref[...]
        upd = cmv < cprev - EPS_IMPROVE
        c_ref[...] = jnp.where(upd, cmv, cprev)
        pv_ref[...] = jnp.where(upd, pv_ref[...], pv_prev_ref[...])
        pjo_ref[...] = jnp.where(upd, pjo_ref[...], pj_prev_ref[...])


def pad_batched_problem(lat, bw, cap, prefix, breq_k, *, tiles=None):
    """Pad the shared network and per-request operands to tile multiples.

    Padded resource rows get BIG latency / zero bandwidth / -1 capacity (never
    feasible); padded k columns and batch rows get BIG prefix/breq (fully
    masked in both the place window and the move).  Returns a dict of padded
    arrays; the padded state must be built by the caller with BIG / -1 fill.
    """
    B, K = prefix.shape
    b_tile, v_tile, w_tile = resolve_tiles(tiles, B)
    n = lat.shape[0]
    nt = max(v_tile, w_tile)
    assert nt % v_tile == 0 and nt % w_tile == 0, (v_tile, w_tile)
    Bp = -(-B // b_tile) * b_tile
    n_pad = -(-n // nt) * nt
    K_pad = -(-K // K_ALIGN) * K_ALIGN
    return dict(
        lat=jnp.full((n_pad, n_pad), BIG, jnp.float32).at[:n, :n].set(lat),
        bw=jnp.zeros((n_pad, n_pad), jnp.float32).at[:n, :n].set(bw),
        cap=jnp.full((n_pad,), -1.0, jnp.float32).at[:n].set(cap),
        prefix=jnp.full((Bp, K_pad), BIG, jnp.float32).at[:B, :K].set(prefix),
        breq_k=jnp.full((Bp, K_pad), BIG, jnp.float32).at[:B, :K].set(breq_k),
    )


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def batched_superstep_pallas(C, par_v, par_j, lat, bw, cap, prefix, breq_k, *,
                             tiles=None, interpret: bool = False):
    """One superstep on PRE-PADDED operands (see pad_batched_problem).

    Shapes: C/par_v/par_j (Bp, n_pad, K_pad); lat/bw (n_pad, n_pad);
    cap (n_pad,); prefix/breq_k (Bp, K_pad).  Returns (Cn, par_vn, par_jn).
    """
    Bp, n_pad, K_pad = C.shape
    b_tile, v_tile, w_tile = resolve_tiles(tiles, Bp)
    assert Bp % b_tile == 0 and n_pad % v_tile == 0, (C.shape, tiles)
    assert n_pad % w_tile == 0 and K_pad % K_ALIGN == 0, (C.shape, tiles)

    P, pj = _place_batched_ref(C, cap, prefix)
    kmajor = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    vmajor_spec = pl.BlockSpec((b_tile, v_tile, K_pad),
                               lambda b, w, v: (b, v, 0))
    net_spec = pl.BlockSpec((v_tile, w_tile), lambda b, w, v: (v, w))
    state_spec = pl.BlockSpec((b_tile, K_pad, w_tile),
                              lambda b, w, v: (b, 0, w))
    grid = (Bp // b_tile, n_pad // w_tile, n_pad // v_tile)
    out = pl.pallas_call(
        _move_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # breq_k, flat
            vmajor_spec, vmajor_spec,  # P, pj
            net_spec, net_spec,  # lat, bw (shared across the batch)
            state_spec, state_spec, state_spec,  # previous C / par_v / par_j
        ],
        out_specs=[state_spec, state_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, K_pad, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((Bp, K_pad, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((Bp, K_pad, n_pad), jnp.int32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="minplus_move_batched",
    )(breq_k.reshape(-1), P, pj, lat, bw, kmajor(C), kmajor(par_v),
      kmajor(par_j))
    return tuple(kmajor(x) for x in out)


# ---------------------------------------------------------------------------
# Fused pure-jnp oracle (off-TPU fast path + CI cross-check)
# ---------------------------------------------------------------------------


def _place_batched_ref(C, cap, prefix):
    """Batched mirror of ``core.leastcost._place_step`` (same op sequence per
    request, so results are bit-identical).  C (B, n, K), prefix (B, K)."""
    B, n, K = C.shape
    P = jnp.full_like(C, BIG)
    pj = jnp.zeros(C.shape, jnp.int32)
    k_idx = jnp.arange(K)
    for x in range(K):
        j_idx = k_idx - x
        valid_j = j_idx >= 0
        shifted = jnp.where(valid_j[None, None, :], jnp.roll(C, x, axis=2), BIG)
        block = prefix - jnp.take(prefix, jnp.maximum(j_idx, 0), axis=1)
        feas = valid_j[None, None, :] & (
            block[:, None, :] <= cap[None, :, None] + EPS_CAP_F32
        )
        cand = jnp.where(feas, shifted, BIG)
        upd = cand < P
        P = jnp.where(upd, cand, P)
        pj = jnp.where(upd, jnp.maximum(j_idx, 0)[None, None, :], pj)
    return P, pj


def _move_batched_ref(P, lat, bw, breq_k):
    """Batched mirror of ``core.leastcost._move_step_ref``: the shared link
    matrices are transposed ONCE and broadcast over the batch — not stacked
    per request as under vmap.  P (B, n, K), breq_k (B, K)."""
    latT = lat.T  # (w, v): reduction over the contiguous axis
    bwT = bw.T

    def one_k(args):
        bk, Pk = args  # (B,), (B, V)
        cand = jnp.where(
            bwT[None, :, :] >= bk[:, None, None],
            latT[None, :, :] + Pk[:, None, :],
            BIG,
        )  # (B, W, V)
        return jnp.min(cand, axis=2), jnp.argmin(cand, axis=2).astype(jnp.int32)

    Cmv_t, pv_t = jax.lax.map(one_k, (breq_k.T, P.transpose(2, 0, 1)))
    return Cmv_t.transpose(1, 2, 0), pv_t.transpose(1, 2, 0)


def batched_superstep_ref(C, par_v, par_j, lat, bw, cap, prefix, breq_k):
    """Fused batched superstep, pure jnp, UNPADDED shapes.  Bit-for-bit equal
    to one ``core.leastcost._superstep`` per request (same tie rules, same
    EPS_IMPROVE threshold); the kernel is cross-checked against this."""
    P, pj = _place_batched_ref(C, cap, prefix)
    Cmv, pv = _move_batched_ref(P, lat, bw, breq_k)
    upd = Cmv < C - EPS_IMPROVE
    pj_of_pv = jnp.take_along_axis(pj, pv, axis=1)
    Cn = jnp.where(upd, Cmv, C)
    par_vn = jnp.where(upd, pv, par_v)
    par_jn = jnp.where(upd, pj_of_pv, par_j)
    return Cn, par_vn, par_jn
