"""Pallas TPU kernel: bandwidth-masked tropical (min,+) matmul with argmin.

The move step of the tensorized BCPM relaxation (see ``core/leastcost.py``):

    C[w, k]  = min_v  P[v, k] + lat[v, w]   s.t.  bw[v, w] >= breq_k[k]
    pv[w, k] = argmin_v

Mapping the paper's per-message set relaxation to the TPU memory hierarchy
(DESIGN.md §5): the naive masked formulation materializes an (n, n, K)
candidate tensor in HBM; this kernel tiles the (w, k) output into VMEM
blocks and streams (v,) reduction tiles through VMEM, fusing the bandwidth
mask and latency shift into the reduction — HBM traffic O(n^2 + nK) instead
of O(n^2 K).  Min-plus has no MXU path, so the reduction runs on the VPU;
all tile dims are multiples of the (8, 128) vreg shape.

Grid: (w_blocks, k_blocks, v_blocks) with v innermost so each (w, k) output
block stays resident in VMEM across its reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.problem import BIG

from jax.experimental.pallas import tpu as pltpu

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)

# Default tile sizes (hillclimbed in EXPERIMENTS.md §Perf; see ops.py).
V_TILE = 128  # reduction tile (v)
W_TILE = 128  # output rows per block (w)
K_TILE = 8  # output cols per block (k) — cand tensor is (V, W, K_TILE)


def _kernel(breq_ref, p_ref, lat_ref, bw_ref, c_ref, pv_ref):
    v_blk = pl.program_id(2)
    # Initialize output block on the first reduction step.
    @pl.when(v_blk == 0)
    def _init():
        c_ref[...] = jnp.full_like(c_ref, BIG)
        pv_ref[...] = jnp.zeros_like(pv_ref)

    p = p_ref[...]  # (V, K)
    lat = lat_ref[...]  # (V, W)
    bw = bw_ref[...]  # (V, W)
    breq = breq_ref[0, :]  # (K,)

    # cand[v, w, k] = P[v, k] + lat[v, w]  where bw[v, w] >= breq[k]
    feas = bw[:, :, None] >= breq[None, None, :]  # (V, W, K)
    cand = jnp.where(feas, p[:, None, :] + lat[:, :, None], BIG)
    cand = jnp.minimum(cand, BIG)  # keep BIG + lat from overflowing to inf
    best = jnp.min(cand, axis=0)  # (W, K)
    arg = jnp.argmin(cand, axis=0).astype(jnp.int32) + v_blk * cand.shape[0]

    prev = c_ref[...]
    take = best < prev  # strict: earlier v-tile wins ties (matches argmin)
    c_ref[...] = jnp.where(take, best, prev)
    pv_ref[...] = jnp.where(take, arg, pv_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("v_tile", "w_tile", "k_tile", "interpret"),
)
def masked_minplus_pallas(
    P,
    lat,
    bw,
    breq_k,
    *,
    v_tile: int = V_TILE,
    w_tile: int = W_TILE,
    k_tile: int = K_TILE,
    interpret: bool = False,
):
    """Padded, tiled pallas_call wrapper.  Shapes: P (n, K), lat/bw (n, n),
    breq_k (K,).  Returns (C (n, K) float32, pv (n, K) int32)."""
    n, K = P.shape
    n_pad = -(-n // max(v_tile, w_tile)) * max(v_tile, w_tile)
    K_pad = -(-K // k_tile) * k_tile

    Pp = jnp.full((n_pad, K_pad), BIG, jnp.float32).at[:n, :K].set(P)
    latp = jnp.full((n_pad, n_pad), BIG, jnp.float32).at[:n, :n].set(lat)
    bwp = jnp.zeros((n_pad, n_pad), jnp.float32).at[:n, :n].set(bw)
    # padded k columns get BIG requirement -> fully masked
    bq = jnp.full((1, K_pad), BIG, jnp.float32).at[0, :K].set(breq_k)

    grid = (n_pad // w_tile, K_pad // k_tile, n_pad // v_tile)
    C, pv = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k_tile), lambda w, k, v: (0, k)),  # breq
            pl.BlockSpec((v_tile, k_tile), lambda w, k, v: (v, k)),  # P
            pl.BlockSpec((v_tile, w_tile), lambda w, k, v: (v, w)),  # lat
            pl.BlockSpec((v_tile, w_tile), lambda w, k, v: (v, w)),  # bw
        ],
        out_specs=[
            pl.BlockSpec((w_tile, k_tile), lambda w, k, v: (w, k)),  # C
            pl.BlockSpec((w_tile, k_tile), lambda w, k, v: (w, k)),  # pv
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, K_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, K_pad), jnp.int32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(bq, Pp, latp, bwp)
    return C[:n, :K], pv[:n, :K]
