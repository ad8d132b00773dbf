"""Unified mapper engine: one entry point over every solver backend.

Before this module the repo had five solver backends with five incompatible
call signatures (``simulator.simulate``, ``leastcost_python``,
``heuristics.anneal/random_k``, ``leastcost_jax[_batched]``,
``distributed.leastcost_shard_map``).  The engine registers each behind a
name and exposes

    solve(rg, df, method="leastcost_jax", **cfg) -> (Mapping | None, Stats)
    solve_batch(rg, dfs, **cfg)                  -> (list[Mapping | None], Stats)

with a single :class:`Stats` dataclass covering rounds / messages /
set sizes / fallbacks across all backends, so callers (``launch/placement``,
``core.online.OnlinePlacer``, benchmarks) never see a backend-specific API.

Registered methods:

  ``exact``             paper Alg. 1-3 (centralized PathMap; exponential)
  ``simulate``          event-driven async simulator (Alg. 4); ``policy=``
                        exact | leastcost | annealed | random_k
  ``leastcost_python``  faithful path-carrying LeastCostMap (§3.4.1)
  ``anneal``            AnnealedLeastCostMap (§3.4.2)
  ``random_k``          RandomNeighbor (§3.4.3)
  ``leastcost_jax``     tensorized (min,+) DP; ``use_kernel=True`` runs the
                        fused batched Pallas superstep (minplus/batched)
  ``shard_map``         decentralized BSP engine on a JAX device mesh

New backends register with :func:`register`; ``solve`` stays the only API.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

from .graph import DataflowPath, Mapping, ResourceGraph
from ..obs import trace as obs_trace


@dataclasses.dataclass
class Stats:
    """Backend-independent solve statistics.

    Fields not meaningful for a backend keep their zero default (e.g. the
    python relaxations send no messages; the simulator has no fallback).
    """

    method: str = ""
    rounds: int = 0  # relaxation rounds / BSP supersteps
    messages_sent: int = 0  # async messages, or BSP async-equivalent count
    messages_processed: int = 0
    messages_pruned: int = 0
    messages_cross_device: int = 0  # BSP: messages crossing a partition
    max_set_size: int = 0  # peak live partial-map states
    maps_generated: int = 0
    # tensorized backends: requests whose device answer failed to backtrack
    # or validate and were re-solved by the host path-carrying solver
    fallbacks: int = 0
    validated: bool = True
    kernel_impl: str = ""  # use_kernel paths: "pallas" | "interpret" | "ref"
    virtual_time: float = 0.0  # simulator virtual completion time
    solve_ms: float = 0.0  # wall clock inside the backend (device + reconstruct)
    # inside solve_ms, batched DP only: host blocked on the device's answer,
    # and the parent-pointer backtrack (+ un-compaction) after it
    dp_wait_ms: float = 0.0
    reconstruct_ms: float = 0.0
    # host-side admission overhead: validation / reserve / commit loops
    # around the solves — the half of admit latency the pipelined path
    # overlaps with the next batch's device work (service-layer counter,
    # filled by OnlinePlacer via engine_stats; zero for bare solves).
    overhead_ms: float = 0.0
    # wall clock spent re-solving optimistic-concurrency conflicts
    # individually after a stale batch solve (service-layer counter).
    conflict_resolve_ms: float = 0.0
    # batches whose in-flight solve was invalidated wholesale by a
    # churn/restore epoch bump and re-solved fresh (service-layer counter).
    stale_batches: int = 0
    batch_size: int = 1
    # node dimension the solve actually ran over — the padded DP/kernel
    # size.  Equals rg.n, or the region-local n_r when a CompactedView was
    # passed: the compaction win the regional plane is graded on
    # (bench_messages solve-size column).
    solve_n: int = 0
    # service-layer counters (repro.service control plane): how much solver
    # work was spent displacing lower-class tickets / re-optimizing the
    # standing allocation, surfaced next to the per-solve numbers so a
    # benchmark row tells the whole admission story.
    preemptions: int = 0  # tickets displaced by higher-class admissions
    defrag_rounds: int = 0  # global re-optimization passes attempted
    # regional control plane (repro.service.regions): cross-region
    # coordination traffic — push-gossip share dissemination and the
    # two-phase commit protocol placing region-spanning dataflows.  Both
    # fold into messages_sent so one column compares a decentralized plane
    # against the per-solve flooding counts of the async simulator.
    gossip_messages: int = 0  # share-estimate pushes (O(R*fanout) per round)
    twopc_messages: int = 0  # reserve/commit/abort traffic for spanning dfs


def _unify(native, method: str) -> Stats:
    """Map any backend's native stats object onto the unified Stats."""
    s = Stats(method=method)
    if native is None:
        return s
    s.rounds = int(getattr(native, "rounds", 0) or getattr(native, "supersteps", 0))
    s.messages_sent = int(
        getattr(native, "messages_sent", 0) or getattr(native, "messages_total", 0)
    )
    s.messages_processed = int(getattr(native, "messages_processed", 0))
    s.messages_pruned = int(getattr(native, "messages_pruned", 0))
    s.messages_cross_device = int(getattr(native, "messages_cross_device", 0))
    s.max_set_size = int(getattr(native, "max_set_size", 0))
    s.maps_generated = int(getattr(native, "total_maps_generated", 0))
    s.fallbacks = int(bool(getattr(native, "fallback_used", False)))
    s.validated = bool(getattr(native, "validated", True))
    s.kernel_impl = str(getattr(native, "kernel_impl", ""))
    s.virtual_time = float(
        getattr(native, "completed_at", None) or getattr(native, "virtual_time", 0.0)
    )
    s.preemptions = int(getattr(native, "preempted", 0))
    s.defrag_rounds = int(getattr(native, "defrag_rounds", 0))
    s.gossip_messages = int(getattr(native, "gossip_messages", 0))
    s.twopc_messages = int(getattr(native, "twopc_messages", 0))
    return s


_REGISTRY: dict[str, Callable] = {}

# Backends that natively batch many requests into one solve in solve_batch
# (everything else falls back to a sequential loop).  Callers that shape
# their batches around native batching (e.g. OnlinePlacer's power-of-two
# bucketing) key off this set rather than hardcoding method names.
BATCHED_METHODS = frozenset({"leastcost_jax"})


def register(name: str):
    """Register ``fn(rg, df, **cfg) -> (Mapping | None, native_stats)``."""

    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


def backends() -> list[str]:
    return sorted(_REGISTRY)


def solve(
    rg: ResourceGraph,
    df: DataflowPath,
    method: str = "leastcost_jax",
    view=None,
    **cfg,
) -> tuple[Optional[Mapping], Stats]:
    """Solve one mapping request with the named backend.

    ``view`` (a :class:`~repro.core.compact.CompactedView`) makes this a
    *region-local* solve: ``rg`` and ``df`` stay in global ids, but the
    backend runs over the view's compacted ``n_r``-node slice and the
    returned mapping is lifted back to global ids.  ``Stats.solve_n``
    records the node dimension the backend actually saw.
    """
    try:
        fn = _REGISTRY[method]
    except KeyError:
        raise ValueError(
            f"unknown mapper backend {method!r}; registered: {backends()}"
        ) from None
    t0 = time.perf_counter()
    if view is not None and not view.is_identity:
        mapping, native = fn(view.compact_graph(rg), view.compact_df(df), **cfg)
        if mapping is not None:
            mapping = view.uncompact_mapping(mapping)
        solve_n = view.n_local
    else:
        mapping, native = fn(rg, df, **cfg)
        solve_n = rg.n
    stats = _unify(native, method)
    stats.solve_n = solve_n
    stats.solve_ms = 1e3 * (time.perf_counter() - t0)
    return mapping, stats


def solve_batch(
    rg: ResourceGraph,
    dfs: list[DataflowPath],
    method: str = "leastcost_jax",
    view=None,
    **cfg,
) -> tuple[list[Optional[Mapping]], Stats]:
    """Solve many requests against one shared network.

    ``leastcost_jax`` batches into a single batched DP (mixed-``p`` requests
    are padded; see ``core.problem``); with ``use_kernel=True`` in ``cfg``
    the fused batched Pallas superstep of ``repro.kernels.minplus.batched``
    replaces the vmapped per-request graph (``Stats.kernel_impl`` records
    which implementation ran).  Every other backend falls back to a
    sequential loop through :func:`solve`.

    ``view`` compacts the whole batch into the view's local id space
    before solving (every request's endpoints must live in the view):
    tiles pad to the region-local ``n_r``, mappings come back global.

    ``graph_tensors`` (in ``cfg``, batched methods only) injects
    device-resident ``{cap, bw, lat}`` so the solve skips the per-batch
    host upload of the network — see :func:`solve_batch_dispatch` for the
    fully asynchronous variant.
    """
    if not dfs:
        return [], Stats(method=method, batch_size=0)
    t0 = time.perf_counter()
    if view is not None and not view.is_identity:
        rg = view.compact_graph(rg)
        dfs = [view.compact_df(d) for d in dfs]
    if method in BATCHED_METHODS:
        from .leastcost import leastcost_jax_batched

        # warm-start seeds live in the caller's (already-local) id space;
        # they cannot survive a view compaction done here
        assert view is None or view.is_identity or "warm_starts" not in cfg
        stats = Stats(method=method)
        mappings = leastcost_jax_batched(rg, list(dfs), stats=stats, **cfg)
    else:
        cfg.pop("graph_tensors", None)  # host-loop backends have no device path
        cfg.pop("warm_starts", None)  # warm seeding is a batched-DP feature
        mappings = []
        stats = Stats(method=method)
        for df in dfs:
            m, st = solve(rg, df, method=method, **cfg)
            mappings.append(m)
            stats.messages_sent += st.messages_sent
            stats.rounds = max(stats.rounds, st.rounds)
            stats.max_set_size = max(stats.max_set_size, st.max_set_size)
            stats.fallbacks += st.fallbacks
            stats.validated &= st.validated
            stats.preemptions += st.preemptions
            stats.defrag_rounds += st.defrag_rounds
            # non-additive: keep the first backend impl seen rather than
            # dropping it on the floor (per-impl counts live in the
            # telemetry registry / OnlineStats.kernel_impls)
            stats.kernel_impl = stats.kernel_impl or st.kernel_impl
    if view is not None and not view.is_identity:
        mappings = [
            view.uncompact_mapping(m) if m is not None else None
            for m in mappings
        ]
    stats.solve_n = rg.n
    stats.batch_size = len(dfs)
    stats.solve_ms = 1e3 * (time.perf_counter() - t0)
    return mappings, stats


class PendingBatchSolve:
    """Handle for an asynchronously dispatched :func:`solve_batch`.

    Batched backends dispatch the device DP and return immediately; the
    host blocks only inside :meth:`finalize` (the commit point).  Backends
    without native batching solve synchronously at dispatch time and
    finalize just hands the stored result back — callers get one uniform
    dispatch/finalize API whatever the backend (the fuzz suites drive the
    pipeline through ``leastcost_python`` this way).
    """

    def __init__(self, method: str, view, dfs, *, pending=None, ready=None,
                 dispatch_ms: float = 0.0):
        self.method = method
        self.view = view
        self.dfs = dfs
        self._pending = pending  # leastcost.PendingDP (batched backends)
        self._ready = ready  # (mappings, Stats) (sync backends)
        self._dispatch_ms = dispatch_ms
        self._solve_n = pending.rg.n if pending is not None else None

    def finalize(self, tracer=obs_trace.NULL
                 ) -> tuple[list[Optional[Mapping]], Stats]:
        """Block until the solve completes; return ``(mappings, stats)``.

        ``stats.solve_ms`` covers dispatch plus the blocking wait and
        reconstruction — the same wall clock :func:`solve_batch` reports,
        minus whatever the caller overlapped between the two halves.
        ``tracer`` takes the ``placer.dp_wait`` / ``placer.reconstruct``
        spans."""
        if self._ready is not None:
            return self._ready
        from .leastcost import leastcost_jax_batched_finalize

        t0 = time.perf_counter()
        stats = Stats(method=self.method)
        mappings = leastcost_jax_batched_finalize(self._pending, stats=stats,
                                                  tracer=tracer)
        if self.view is not None and not self.view.is_identity:
            t1 = time.perf_counter()
            with tracer.span("reconstruct", track="placer"):
                mappings = [
                    self.view.uncompact_mapping(m) if m is not None else None
                    for m in mappings
                ]
            stats.reconstruct_ms += 1e3 * (time.perf_counter() - t1)
        stats.solve_n = self._solve_n
        stats.batch_size = len(self.dfs)
        stats.solve_ms = self._dispatch_ms + 1e3 * (time.perf_counter() - t0)
        self._ready = (mappings, stats)
        self._pending = None
        return self._ready


def solve_batch_dispatch(
    rg: ResourceGraph,
    dfs: list[DataflowPath],
    method: str = "leastcost_jax",
    view=None,
    graph_tensors=None,
    **cfg,
) -> PendingBatchSolve:
    """Asynchronous :func:`solve_batch`: dispatch now, block at
    :meth:`PendingBatchSolve.finalize`.

    On batched backends the device computation starts immediately (JAX
    async dispatch) while the caller keeps the host busy — the online
    placer overlaps batch k+1's solve with batch k's validation/commit.
    ``graph_tensors`` injects device-resident network tensors (see
    ``core.residual.ResidualState``) so the dispatch ships only the O(p)
    request tensors.  Non-batching backends run synchronously here.
    """
    if not dfs:
        return PendingBatchSolve(method, view, [],
                                 ready=([], Stats(method=method, batch_size=0)))
    if method in BATCHED_METHODS:
        from .leastcost import leastcost_jax_batched_dispatch

        t0 = time.perf_counter()
        if view is not None and not view.is_identity:
            assert graph_tensors is None, "view compaction vs device tensors"
            assert "warm_starts" not in cfg, "warm seeds vs view compaction"
            rg = view.compact_graph(rg)
            dfs = [view.compact_df(d) for d in dfs]
        pending = leastcost_jax_batched_dispatch(
            rg, list(dfs), graph_tensors=graph_tensors, **cfg
        )
        return PendingBatchSolve(
            method, view, list(dfs), pending=pending,
            dispatch_ms=1e3 * (time.perf_counter() - t0),
        )
    ready = solve_batch(rg, list(dfs), method=method, view=view, **cfg)
    return PendingBatchSolve(method, view, list(dfs), ready=ready)


# ---------------------------------------------------------------------------
# Backend adapters
# ---------------------------------------------------------------------------


@register("exact")
def _exact(rg, df, **cfg):
    from .exact import pathmap_exact

    return pathmap_exact(rg, df, **cfg)


@register("simulate")
def _simulate(rg, df, **cfg):
    from .simulator import SimConfig, simulate

    sim_cfg = cfg.pop("cfg", None) or SimConfig(**cfg)
    return simulate(rg, df, sim_cfg)


@register("leastcost_python")
def _leastcost_python(rg, df, **cfg):
    from .leastcost import leastcost_python

    return leastcost_python(rg, df, **cfg)


@register("anneal")
def _anneal(rg, df, **cfg):
    from .heuristics import anneal_python

    return anneal_python(rg, df, **cfg)


@register("random_k")
def _random_k(rg, df, **cfg):
    from .heuristics import random_k_python

    return random_k_python(rg, df, **cfg)


@register("leastcost_jax")
def _leastcost_jax(rg, df, **cfg):
    from .leastcost import leastcost_jax

    return leastcost_jax(rg, df, **cfg)


@register("shard_map")
def _shard_map_backend(rg, df, **cfg):
    from .distributed import leastcost_shard_map

    return leastcost_shard_map(rg, df, **cfg)
