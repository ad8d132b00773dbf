"""LeastCostMap heuristic (paper §3.4.1), two implementations.

1. ``leastcost_python`` — faithful path-carrying version: the exact PathMap
   relaxation but with ``M(u, j)`` pruned to the single cheapest partial map
   per (node, prefix-length).  Complexity ``O(n·e·p^2)``; sound w.r.t. the
   cumulative-capacity constraint because the route is carried in the state.

2. ``leastcost_jax`` — beyond-paper tensorized dynamic program over the
   tropical (min,+) semiring so the relaxation runs on TPU vector units.
   State ``C[v, j]`` = min cost of placing the first ``j`` dataflow nodes on
   a route ending at ``v``.  One superstep is

       place:  P[v,k]  = min_{j<=k, s[k]-s[j] <= cap[v]}  C[v,j]
       move:   C'[w,k] = min_{v != w, bw[v,w] >= breq[k-1]}  P[v,k] + lat[v,w]

   iterated to fixpoint (<= n-1 supersteps, Lemma 3.2).  On the kernel path
   (``use_kernel=True``) the whole superstep runs as the fused batched
   Pallas kernel of ``repro.kernels.minplus.batched`` — the single-step
   kernels in ``kernels/minplus``/``kernels/place`` remain as step-level
   oracles only.  Parent pointers are tracked for reconstruction; anomaly
   handling (broken chain / revisit) lives in ``core.reconstruct``.

Shared constants/tensors come from ``core.problem``; ``leastcost_jax_batched``
solves many (possibly mixed-``p``) requests on one shared network in one
batched DP — the continuous-arrival path behind ``core.online.OnlinePlacer``.
With ``use_kernel=True`` the move and monotone update run as the batched
Pallas kernel of ``repro.kernels.minplus.batched`` (grid over (batch, w, v)
with network tiles shared across the batch); off-TPU the kernel's fused-jnp
mirror replaces the vmapped per-request graph.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .graph import (
    INF,
    DataflowPath,
    Mapping,
    ResourceGraph,
    mapping_cost,
    validate_mapping,
)
from .problem import (
    BIG,
    EPS_BW,
    EPS_CAP_F32,
    EPS_COST,
    EPS_IMPROVE,
    make_cap_ok,
    problem_tensors,
    stack_requests,
    BATCH_IN_AXES,
)
from .device import on_tpu
from .reconstruct import reconstruct_mapping
from ..obs import trace as obs_trace


@dataclasses.dataclass
class HeuristicStats:
    max_set_size: int = 0
    total_maps_generated: int = 0
    rounds: int = 0
    fallback_used: bool = False
    validated: bool = True
    kernel_impl: str = ""  # "", "pallas", "interpret", or "ref"


# ---------------------------------------------------------------------------
# 1. Faithful path-carrying LeastCostMap (centralized, paper §3.4.1)
# ---------------------------------------------------------------------------


def leastcost_python(
    rg: ResourceGraph, df: DataflowPath
) -> tuple[Optional[Mapping], HeuristicStats]:
    p, n = df.p, rg.n
    src, dst = df.src, df.dst
    stats = HeuristicStats()
    # M[u][j] = (cost, assign, route) | None — single cheapest per (u, j).
    M: list[list[Optional[tuple]]] = [[None] * (p + 1) for _ in range(n)]
    best: Optional[Mapping] = None

    cap_ok = make_cap_ok(rg, df)

    for j in range(1, p):
        if not cap_ok(0, j, src):
            break
        M[src][j] = (0.0, (src,) * j, (src,))
        stats.total_maps_generated += 1
    if cap_ok(0, p, src) and src == dst:
        best = Mapping((src,) * p, (src,), 0.0)

    edges = list(rg.edges())
    fresh = {(src, j) for j in range(1, p) if M[src][j]}
    for rnd in range(n - 1):
        stats.rounds = rnd + 1
        new_fresh: set[tuple[int, int]] = set()
        for (u, v) in edges:
            for j in range(1, p):
                if (u, j) not in fresh or M[u][j] is None:
                    continue
                if float(rg.bw[u, v]) + EPS_BW < float(df.breq[j - 1]):
                    continue
                cost, assign, route = M[u][j]
                if v in route:
                    continue
                ncost = cost + float(rg.lat[u, v])
                if v == dst:
                    if cap_ok(j, p, v):
                        m = Mapping(assign + (v,) * (p - j), route + (v,), ncost)
                        if best is None or m.cost < best.cost:
                            best = m
                else:
                    for x in range(0, p - j):
                        if not cap_ok(j, j + x, v):
                            break
                        cur = M[v][j + x]
                        if cur is None or ncost < cur[0] - EPS_COST:
                            M[v][j + x] = (ncost, assign + (v,) * x, route + (v,))
                            stats.total_maps_generated += 1
                            new_fresh.add((v, j + x))
        stats.max_set_size = max(
            stats.max_set_size, sum(1 for row in M for e in row if e is not None)
        )
        fresh = new_fresh
        if not fresh:
            break
    return best, stats


# ---------------------------------------------------------------------------
# 2. Tensorized JAX DP (beyond paper)
# ---------------------------------------------------------------------------

# Tensor keys carrying a warm-start cost frontier (see
# :func:`warm_seed_from_mapping`).  Their presence in the ``tensors`` dict
# is a python-level (trace-time) condition, so warm and cold solves compile
# as separate specializations and the cold path is byte-identical to before.
_WARM_KEYS = ("warm_v", "warm_j", "warm_c", "warm_pv", "warm_pj")
_WARM_IN_AXES = {k: 0 for k in _WARM_KEYS}


def warm_seed_from_mapping(rg: ResourceGraph, df: DataflowPath, mapping):
    """Host-side O(p + route) walk turning a previously-committed (now
    possibly infeasible) mapping into a DP cost frontier.

    Walks the mapping's route edge by edge under the *current* residual
    ``rg``, emitting one seed state per arrival ``(v, j, cost)`` with its
    parent ``(u, j_prev)`` — exactly the arrival states the cold DP would
    rediscover — and stops at the first constraint violation (capacity
    window, bandwidth gate, dead link, or route exhaustion).  Every seeded
    state is achievable under the current residual, so seeding ``C0`` with
    it preserves the DP invariant "C[v,j] is a realizable cost" and the
    relaxation can only improve on it.  Returns a seed dict (numpy arrays
    ``v/j/cost/pv/pj``) or None when not even the first hop survives.
    """
    assign, route = mapping.assign, mapping.route
    cap, bw, lat = rg.cap, rg.bw, rg.lat
    p = df.p
    sv, sj, sc, spv, spj = [], [], [], [], []
    pos = 0  # last df node whose outgoing edge has been carried
    prev_j = 0  # arrival prefix length at the current route node
    cost = np.float32(0.0)
    for u, w in zip(route[:-1], route[1:]):
        while pos + 1 < p and assign[pos + 1] == u:
            pos += 1
        # df nodes placed at u this visit: prev_j .. pos inclusive
        block = float(np.sum(df.creq[prev_j:pos + 1], dtype=np.float64))
        if block > float(cap[u]) + EPS_CAP_F32:
            break
        if pos >= p - 1:
            break  # nothing left to move; dst tail handled by the DP
        lw = float(lat[u, w])
        if not np.isfinite(lw):
            break
        if float(bw[u, w]) < float(df.breq[pos]):
            break  # same exact gate as the DP move step
        cost = np.float32(cost + np.float32(lw))
        sv.append(w)
        sj.append(pos + 1)
        sc.append(cost)
        spv.append(u)
        spj.append(prev_j)
        prev_j = pos + 1
    if not sv:
        return None
    return {
        "v": np.asarray(sv, np.int32), "j": np.asarray(sj, np.int32),
        "cost": np.asarray(sc, np.float32),
        "pv": np.asarray(spv, np.int32), "pj": np.asarray(spj, np.int32),
    }


def stack_warm_seeds(warm_starts, B: int, p_max: int) -> dict:
    """Stack per-request seed dicts (None = no seed) into padded (B, S)
    device tensors.  S is power-of-two padded so the stream of varying
    seed lengths compiles at most log2(max route) warm specializations.
    Pad slots use ``cost=BIG`` + parents ``-1``: ``_apply_warm`` merges
    with ``.min``/``.max``, so a pad slot is provably a no-op against the
    cold init (``C0=BIG``, parents ``-1``)."""
    S = 1
    for w in warm_starts:
        if w is not None and len(w["v"]) > S:
            S = len(w["v"])
    S = 1 << (S - 1).bit_length()
    wv = np.zeros((B, S), np.int32)
    wj = np.zeros((B, S), np.int32)
    wc = np.full((B, S), BIG, np.float32)
    wpv = np.full((B, S), -1, np.int32)
    wpj = np.full((B, S), -1, np.int32)
    for b in range(min(B, len(warm_starts))):
        w = warm_starts[b]
        if w is None:
            continue
        s = len(w["v"])
        wv[b, :s] = w["v"]
        wj[b, :s] = w["j"]
        wc[b, :s] = w["cost"]
        wpv[b, :s] = w["pv"]
        wpj[b, :s] = w["pj"]
    return {
        "warm_v": jnp.asarray(wv), "warm_j": jnp.asarray(wj),
        "warm_c": jnp.asarray(wc), "warm_pv": jnp.asarray(wpv),
        "warm_pj": jnp.asarray(wpj),
    }


def _apply_warm(C0, pv0, pj0, tensors):
    """Merge a warm-start frontier into the cold DP init.  ``min`` on
    costs keeps the invariant that every finite C entry is realizable;
    ``max`` on parents is exact because real seeds target distinct
    ``(v, j)`` cells (a simple route visits each node once) whose cold
    parents are ``-1``, and pad slots carry ``-1``/``BIG`` no-ops."""
    wv, wj = tensors["warm_v"], tensors["warm_j"]
    wc, wpv, wpj = tensors["warm_c"], tensors["warm_pv"], tensors["warm_pj"]
    if C0.ndim == 3:  # batched (B, n, K)
        b = jnp.arange(C0.shape[0])[:, None]
        return (C0.at[b, wv, wj].min(wc),
                pv0.at[b, wv, wj].max(wpv),
                pj0.at[b, wv, wj].max(wpj))
    return (C0.at[wv, wj].min(wc),
            pv0.at[wv, wj].max(wpv),
            pj0.at[wv, wj].max(wpj))


def _place_step(C, cap, prefix):
    """P[v,k] = min over x>=0 of C[v,k-x] s.t. prefix[k]-prefix[k-x] <= cap[v].

    Also returns pj[v,k] = the achieving j = k-x.  Unrolled over x (p is
    static and small); O(n p^2) work, O(n p) memory.
    """
    n, P1 = C.shape
    P = jnp.full_like(C, BIG)
    pj = jnp.zeros(C.shape, jnp.int32)
    k_idx = jnp.arange(P1)
    for x in range(P1):
        j_idx = k_idx - x
        valid_j = j_idx >= 0
        shifted = jnp.where(
            valid_j[None, :], jnp.roll(C, x, axis=1), BIG
        )  # shifted[v,k] = C[v,k-x]
        block = prefix[k_idx] - prefix[jnp.maximum(j_idx, 0)]
        feas = valid_j[None, :] & (block[None, :] <= cap[:, None] + EPS_CAP_F32)
        cand = jnp.where(feas, shifted, BIG)
        upd = cand < P
        P = jnp.where(upd, cand, P)
        pj = jnp.where(upd, jnp.maximum(j_idx, 0)[None, :], pj)
    return P, pj


# §Perf hillclimb C (EXPERIMENTS.md): C2 (fused (n,n,K) pass) was REFUTED on
# CPU — 2x slower than the k-loop (cache blowout); C4 below transposes the
# min-reduction onto the contiguous axis instead.
_BATCHED_MOVE_LIMIT = 0  # C2 disabled; the TPU Pallas kernel tiles explicitly


def _move_step_ref(P, lat, bw, breq):
    """C'[w,k] = min_v P[v,k] + lat[v,w] s.t. bw[v,w] >= breq[k-1]; plus argmin.

    Pure-jnp oracle for the Pallas kernel.  k = 0 column is invalid (no
    dataflow edge precedes node 0) -> BIG.  C4: the reduction runs over the
    minor (contiguous) axis of the transposed link matrices — XLA hoists the
    loop-invariant transposes out of the relaxation while-loop.
    """
    n, P1 = P.shape
    # breq_k[k] = requirement of the dataflow edge carried when k nodes are
    # placed (edge (k-1, k)); k=0 and k=p get BIG (no move possible).
    breq_k = jnp.concatenate(
        [jnp.full((1,), BIG), breq, jnp.full((P1 - 1 - breq.shape[0],), BIG)]
    )
    if n * n * P1 <= _BATCHED_MOVE_LIMIT:
        # single fused pass over (v, w, k)
        cand = jnp.where(
            bw[:, :, None] >= breq_k[None, None, :],
            P[:, None, :] + lat[:, :, None],
            BIG,
        )
        return jnp.min(cand, axis=0), jnp.argmin(cand, axis=0).astype(jnp.int32)

    latT = lat.T  # (w, v): reduction axis contiguous
    bwT = bw.T

    def one_k(args):
        bk, Pk = args
        cand = jnp.where(bwT >= bk, latT + Pk[None, :], BIG)  # (w, v)
        return jnp.min(cand, axis=1), jnp.argmin(cand, axis=1).astype(jnp.int32)

    # lax.map with O(n^2) live slabs: measured best on CPU (C2 fused-3D and
    # C5 vmap-over-k both refuted — cache blowout; EXPERIMENTS.md §Perf C).
    Cmv_t, pv_t = jax.lax.map(one_k, (breq_k, P.T))
    return Cmv_t.T, pv_t.T


def _superstep(state, tensors):
    C, par_v, par_j, changed = state
    P, pj = _place_step(C, tensors["cap"], tensors["prefix"])
    Cmv, pv = _move_step_ref(P, tensors["lat"], tensors["bw"], tensors["breq"])
    upd = Cmv < C - EPS_IMPROVE
    Cn = jnp.where(upd, Cmv, C)
    # parent arrival state of (w,k) is (pv[w,k], pj[pv[w,k],k])
    pj_of_pv = pj[pv, jnp.arange(C.shape[1])[None, :]]
    par_vn = jnp.where(upd, pv, par_v)
    par_jn = jnp.where(upd, pj_of_pv, par_j)
    return Cn, par_vn, par_jn, jnp.any(upd)


@functools.partial(jax.jit, static_argnames=("n", "p", "max_rounds"))
def _leastcost_dp(tensors, n: int, p: int, max_rounds: int):
    """Run the relaxation to fixpoint (pure-jnp path).  ``p`` is the static
    column count; ``tensors["p_eff"]`` is the (possibly traced, per-request)
    true dataflow length — the final reduction at ``dst`` only reads columns
    ``< p_eff``, so padded mixed-``p`` batches share one compiled DP.  The
    kernel path lives in :func:`_leastcost_dp_batched` (``use_kernel=True``
    routes there, with B=1 for single requests)."""
    C0 = jnp.full((n, p + 1), BIG, jnp.float32)
    # arrival state at src with 0 nodes placed costs 0
    C0 = C0.at[tensors["src"], 0].set(0.0)
    par_v0 = jnp.full((n, p + 1), -1, jnp.int32)
    par_j0 = jnp.full((n, p + 1), -1, jnp.int32)
    if "warm_v" in tensors:
        C0, par_v0, par_j0 = _apply_warm(C0, par_v0, par_j0, tensors)

    def cond(carry):
        t, (C, pv, pj, changed) = carry
        return (t < max_rounds) & changed

    def body(carry):
        t, state = carry
        state = _superstep((state[0], state[1], state[2], state[3]), tensors)
        return t + 1, state

    t, (C, par_v, par_j, _) = jax.lax.while_loop(
        cond, body, (0, (C0, par_v0, par_j0, jnp.array(True)))
    )
    # answer: min over j<p_eff of C[dst, j] + place nodes j..p_eff-1 on dst
    prefix = tensors["prefix"]
    p_eff = tensors.get("p_eff", jnp.asarray(p, jnp.int32))
    j_idx = jnp.arange(p + 1)
    cap_dst = tensors["cap"][tensors["dst"]]
    feas = (j_idx < p_eff) & (prefix[p_eff] - prefix[j_idx] <= cap_dst + EPS_CAP_F32)
    final = jnp.where(feas, C[tensors["dst"], :], BIG)
    best_j = jnp.argmin(final)
    return C, par_v, par_j, final[best_j], best_j, t


@functools.lru_cache(maxsize=None)
def _vmapped_dp(n: int, p: int, max_rounds: int, warm: bool = False):
    """Cached jit-of-vmap of the per-request DP: without the outer jit the
    python-level vmap batching trace re-runs on every call, a measurable
    per-batch overhead on the online placer's hot path.  ``warm=True``
    expects the ``_WARM_KEYS`` frontier tensors batched along axis 0."""
    axes = dict(BATCH_IN_AXES, **_WARM_IN_AXES) if warm else BATCH_IN_AXES

    # named, so the device module reads jit_vmapped_leastcost_dp in traces
    def vmapped_leastcost_dp(t):
        return _leastcost_dp(t, n=n, p=p, max_rounds=max_rounds)

    return jax.jit(jax.vmap(vmapped_leastcost_dp, in_axes=(axes,)))


@functools.partial(
    jax.jit, static_argnames=("B", "n", "p", "max_rounds", "impl", "tiles")
)
def _leastcost_dp_batched(tensors, B: int, n: int, p: int, max_rounds: int,
                          impl: str = "ref", tiles=None):
    """Run B requests' relaxations to fixpoint with ONE fused batched
    superstep per round (``repro.kernels.minplus.batched``): the shared
    ``lat``/``bw`` tiles serve the whole batch instead of being re-streamed
    per request under vmap.

    ``impl``: "pallas" (TPU), "interpret" (Pallas interpreter — the CPU-CI
    cross-check path), or "ref" (fused jnp oracle, the fast off-TPU path).
    All three produce bit-identical results to the vmapped jnp DP.
    """
    from repro.kernels.minplus import batched as _batched

    K = p + 1
    lat, bw, cap = tensors["lat"], tensors["bw"], tensors["cap"]
    prefix = tensors["prefix"]  # (B, K)
    # breq_k[b, k] = bandwidth of the dataflow edge carried when k nodes are
    # placed (edge (k-1, k)); k = 0 and k = p get BIG (no move possible).
    breq_k = jnp.concatenate(
        [jnp.full((B, 1), BIG, jnp.float32), tensors["breq"],
         jnp.full((B, 1), BIG, jnp.float32)], axis=1)

    C0 = jnp.full((B, n, K), BIG, jnp.float32)
    C0 = C0.at[jnp.arange(B), tensors["src"], 0].set(0.0)
    pv0 = jnp.full((B, n, K), -1, jnp.int32)
    pj0 = jnp.full((B, n, K), -1, jnp.int32)
    if "warm_v" in tensors:
        # warm frontier merged before the kernel-path fill(), so the padded
        # state inherits the seeds too
        C0, pv0, pj0 = _apply_warm(C0, pv0, pj0, tensors)

    if impl == "ref":
        step = functools.partial(
            _batched.batched_superstep_ref,
            lat=lat, bw=bw, cap=cap, prefix=prefix, breq_k=breq_k)
        state0 = (C0, pv0, pj0)
    else:
        pads = _batched.pad_batched_problem(
            lat, bw, cap, prefix, breq_k, tiles=tiles)
        Bp, K_pad = pads["prefix"].shape
        n_pad = pads["lat"].shape[0]
        fill = lambda x, v: jnp.full(  # noqa: E731
            (Bp, n_pad, K_pad), v, x.dtype).at[:B, :n, :K].set(x)
        step = functools.partial(
            _batched.batched_superstep_pallas,
            lat=pads["lat"], bw=pads["bw"], cap=pads["cap"],
            prefix=pads["prefix"], breq_k=pads["breq_k"],
            tiles=tiles, interpret=(impl == "interpret"))
        state0 = (fill(C0, BIG), fill(pv0, -1), fill(pj0, -1))

    def cond(carry):
        t, C, pv, pj, changed = carry
        return (t < max_rounds) & changed

    def body(carry):
        t, C, pv, pj, _ = carry
        Cn, pvn, pjn = step(C, pv, pj)
        # the EPS_IMPROVE update is monotone, so any change is a decrease
        return t + 1, Cn, pvn, pjn, jnp.any(Cn < C)

    # named scope = free trace-time metadata: the relaxation loop shows up
    # as one labeled block in XLA/Perfetto profiles (the placer's
    # placer.dispatch span marks the host side; this labels the compiled
    # computation itself)
    with jax.named_scope(f"minplus_dp_batched[{impl}]"):
        t, Cp, pvp, pjp, _ = jax.lax.while_loop(
            cond, body, (0, *state0, jnp.array(True))
        )
    C, par_v, par_j = Cp[:B, :n, :K], pvp[:B, :n, :K], pjp[:B, :n, :K]

    # answer per request: min over j<p_eff of C[dst, j] + tail placed on dst
    p_eff = tensors["p_eff"]  # (B,)
    j_idx = jnp.arange(K)
    pre_pe = jnp.take_along_axis(prefix, p_eff[:, None], axis=1)  # (B, 1)
    cap_dst = cap[tensors["dst"]]  # (B,)
    feas = (j_idx[None, :] < p_eff[:, None]) & (
        pre_pe - prefix <= cap_dst[:, None] + EPS_CAP_F32
    )
    C_dst = C[jnp.arange(B), tensors["dst"], :]  # (B, K)
    final = jnp.where(feas, C_dst, BIG)
    best_j = jnp.argmin(final, axis=1)
    best_cost = jnp.take_along_axis(final, best_j[:, None], axis=1)[:, 0]
    return C, par_v, par_j, best_cost, best_j, t


@dataclasses.dataclass(eq=False)
class PendingDP:
    """An in-flight batched DP: device arrays dispatched, not yet synced.

    Produced by :func:`leastcost_jax_batched_dispatch`; holds everything
    :func:`leastcost_jax_batched_finalize` needs to block, pull parent
    pointers to host, and reconstruct mappings.  The jnp fields are
    immutable device arrays over the tensors captured at dispatch time, so
    later residual mutations cannot corrupt an in-flight solve — the basis
    of the online placer's cross-batch optimistic pipeline.
    """

    rg: ResourceGraph  # host residual snapshot (reconstruction/validation)
    dfs: list
    par_v: object  # (B, n, K) device array
    par_j: object
    best_cost: object  # (B,) device array
    best_j: object
    rounds: object  # device scalar (kernel) | (B,) array (vmapped) | None
    kernel_impl: str = ""
    validate: bool = True
    warm: bool = False  # True iff this solve was warm-start seeded


def leastcost_jax_batched_dispatch(
    rg: ResourceGraph,
    dfs: list,
    *,
    validate: bool = True,
    max_rounds: Optional[int] = None,
    use_kernel: bool = False,
    tiles=None,
    bucket_batch: bool = False,
    graph_tensors=None,
    warm_starts=None,
) -> PendingDP:
    """Dispatch the batched DP without waiting for the result.

    JAX dispatch is asynchronous: the returned :class:`PendingDP` holds
    unblocked device arrays, so the caller can overlap host-side work
    (validating/committing a previous batch) with the device computation
    and only synchronize in :func:`leastcost_jax_batched_finalize`.

    ``graph_tensors`` injects device-resident ``{cap, bw, lat}`` (see
    ``core.residual.ResidualState.device_tensors``) so the dispatch ships
    only the O(p) per-request tensors; ``rg`` is still required as the host
    graph the reconstruction loop walks.

    ``warm_starts`` (optional, aligned with ``dfs``) seeds the DP's cost
    frontier per request — tier 2 of the incremental admission fast path.
    Each entry is None, a seed dict from :func:`warm_seed_from_mapping`,
    or a previously-committed ``Mapping`` (converted here against ``rg``).
    Combine with a small ``max_rounds`` to run a bounded number of
    correction supersteps instead of a full cold relaxation; the caller
    falls back to a cold solve for requests the bounded pass cannot place.
    """
    assert dfs
    n = rg.n
    B = len(dfs)
    if bucket_batch:
        B = 1 << (B - 1).bit_length()  # next power of two
    tensors, p_max = stack_requests(rg, dfs, pad_to=B,
                                    graph_tensors=graph_tensors)
    warm = False
    if warm_starts is not None:
        seeds = [
            w if (w is None or isinstance(w, dict))
            else warm_seed_from_mapping(rg, df, w)
            for w, df in zip(warm_starts, dfs)
        ]
        if any(s is not None for s in seeds):
            tensors = dict(tensors, **stack_warm_seeds(seeds, B, p_max))
            warm = True
    max_rounds = max_rounds or (n - 1 if n > 1 else 1)
    impl = ""
    if use_kernel:
        impl = "pallas" if on_tpu() else "ref"
        C, par_v, par_j, best_cost, best_j, rounds = _leastcost_dp_batched(
            tensors, B=B, n=n, p=p_max, max_rounds=max_rounds,
            impl=impl, tiles=tiles,
        )
    else:
        fn = _vmapped_dp(n, p_max, max_rounds, warm)
        C, par_v, par_j, best_cost, best_j, rounds = fn(tensors)
    return PendingDP(rg, list(dfs), par_v, par_j, best_cost, best_j,
                     rounds, kernel_impl=impl, validate=validate, warm=warm)


def leastcost_jax_batched_finalize(pending: PendingDP, stats=None,
                                   tracer=obs_trace.NULL) -> list:
    """Block on an in-flight batched DP and reconstruct its mappings.

    This is the only host synchronization point of the batched path: the
    ``np.asarray`` pulls force completion of the dispatched computation
    (the pipelined placer's commit-time ``block_until_ready``).  The wait
    and the reconstruction are timed into ``stats.dp_wait_ms`` /
    ``stats.reconstruct_ms`` and traced as ``placer.dp_wait`` /
    ``placer.reconstruct``."""
    t0 = time.perf_counter()
    with tracer.span("dp_wait", track="placer", cat="solve"):
        par_v, par_j = np.asarray(pending.par_v), np.asarray(pending.par_j)
        best_cost = np.asarray(pending.best_cost)
        best_j = np.asarray(pending.best_j)
        rounds = (None if pending.rounds is None
                  else np.asarray(pending.rounds))
    t1 = time.perf_counter()
    if stats is not None and rounds is not None:
        if pending.kernel_impl:
            stats.kernel_impl = pending.kernel_impl
        # kernel path: one shared device scalar; vmapped path: (B,) per-
        # request superstep counts — report the batch's slowest request
        stats.rounds = int(np.max(rounds))
    out = []
    with tracer.span("reconstruct", track="placer", cat="solve"):
        for i, df in enumerate(pending.dfs):
            per = HeuristicStats()
            out.append(
                reconstruct_mapping(
                    pending.rg, df, par_v[i], par_j[i],
                    float(best_cost[i]), int(best_j[i]),
                    validate=pending.validate, stats=per,
                )
            )
            if stats is not None:
                stats.fallbacks += int(per.fallback_used)
                stats.validated &= per.validated
    if stats is not None:
        stats.dp_wait_ms = 1e3 * (t1 - t0)
        stats.reconstruct_ms = 1e3 * (time.perf_counter() - t1)
    return out


def leastcost_jax_batched(
    rg: ResourceGraph,
    dfs: list,
    *,
    validate: bool = True,
    max_rounds: Optional[int] = None,
    use_kernel: bool = False,
    tiles=None,
    bucket_batch: bool = False,
    stats=None,
    graph_tensors=None,
    warm_starts=None,
) -> list:
    """Solve many mapping requests on ONE shared resource network in a
    single vmapped DP (§Perf C6): the realistic continuous-arrival case —
    link matrices are shared across the batch, so the per-request marginal
    cost is one (n, p_max) state tensor.  Requests of mixed ``p`` are padded
    (``core.problem.pad_request``).  Returns a list of (Mapping | None).

    Implemented as dispatch + finalize (see
    :func:`leastcost_jax_batched_dispatch`): callers that want to overlap
    the device solve with host work use the two halves directly.

    ``use_kernel=True`` selects the fused batched superstep path
    (``repro.kernels.minplus.batched``) instead of vmapping the per-request
    DP: the compiled Pallas kernel on TPU, its fused-jnp mirror elsewhere.
    ``tiles`` = (b_tile, v_tile, w_tile) for the Pallas grid.

    ``bucket_batch=True`` pads the batch dimension to the next power of two
    at the TENSOR level (dummy rows, ignored by the reconstruction loop), so
    a stream of varying micro-batch sizes compiles at most log2(max batch)
    DP specializations — the online placer's admission path sets this.

    ``stats`` (optional, e.g. the engine's unified ``Stats``) aggregates
    anomaly signals across the batch: ``fallbacks`` counts the requests
    that needed the path-carrying rescue, ``validated`` is cleared if ANY
    reconstruction failed validation."""
    pending = leastcost_jax_batched_dispatch(
        rg, dfs, validate=validate, max_rounds=max_rounds,
        use_kernel=use_kernel, tiles=tiles,
        bucket_batch=bucket_batch, graph_tensors=graph_tensors,
        warm_starts=warm_starts,
    )
    return leastcost_jax_batched_finalize(pending, stats=stats)


def leastcost_jax(
    rg: ResourceGraph,
    df: DataflowPath,
    *,
    use_kernel: bool = False,
    tiles=None,
    max_rounds: Optional[int] = None,
    validate: bool = True,
    warm_start=None,
) -> tuple[Optional[Mapping], HeuristicStats]:
    """Tensorized LeastCostMap.  Returns (mapping | None, stats).

    ``use_kernel=True`` runs the fused batched superstep path with B=1 —
    the same code path that serves ``leastcost_jax_batched`` (B is a static
    jit argument, so B=1 compiles its own specialization; the online
    placer's recompile bound comes from ``admit_many``'s power-of-two
    batch bucketing).

    ``warm_start`` (a seed dict from :func:`warm_seed_from_mapping` or a
    prior ``Mapping``) seeds the DP frontier; pair with a small
    ``max_rounds`` for a bounded correction solve.
    """
    n, p = rg.n, df.p
    stats = HeuristicStats()
    max_rounds = max_rounds or (n - 1 if n > 1 else 1)
    if warm_start is not None and not isinstance(warm_start, dict):
        warm_start = warm_seed_from_mapping(rg, df, warm_start)
    if use_kernel:
        impl = "pallas" if on_tpu() else "ref"
        stats.kernel_impl = impl
        tensors, _ = stack_requests(rg, [df])
        if warm_start is not None:
            tensors = dict(tensors, **stack_warm_seeds([warm_start], 1, p))
        Cb, par_vb, par_jb, best_costb, best_jb, rounds = _leastcost_dp_batched(
            tensors, B=1, n=n, p=p, max_rounds=max_rounds, impl=impl,
            tiles=tiles,
        )
        C, par_v, par_j = Cb[0], par_vb[0], par_jb[0]
        best_cost, best_j = best_costb[0], best_jb[0]
    else:
        tensors = problem_tensors(rg, df)
        if warm_start is not None:
            batched = stack_warm_seeds([warm_start], 1, p)
            tensors = dict(tensors, **{k: v[0] for k, v in batched.items()})
        C, par_v, par_j, best_cost, best_j, rounds = _leastcost_dp(
            tensors, n=n, p=p, max_rounds=max_rounds
        )
    stats.rounds = int(rounds)
    stats.max_set_size = int(np.sum(np.asarray(C) < BIG / 2))
    if float(best_cost) >= BIG / 2:
        return None, stats
    # Backtrack parent pointers; on a broken chain or revisit anomaly the
    # sound path-carrying version is substituted (rare; counted in stats).
    m = reconstruct_mapping(
        rg, df, par_v, par_j, float(best_cost), int(best_j),
        validate=validate, stats=stats,
    )
    return m, stats
