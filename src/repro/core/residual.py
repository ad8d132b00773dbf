"""Device-resident residual tensors with a versioned host mirror.

:class:`ResidualState` is the single owner of the online placer's residual
capacity/bandwidth state.  The float64 host arrays remain the source of
truth — every commit/release mutates them immediately, and validation at
commit time always reads them — but the float32 tensors the batched DP
consumes (``cap``/``bw``/``lat`` with liveness applied) are kept *device
resident*: commits accumulate into a small delta buffer that is applied as
one scatter-add the next time a solve is dispatched, instead of re-uploading
the full O(n^2) residual every micro-batch.

Two counters version the state:

- ``version`` bumps on **every** host mutation (commit, release, liveness
  change, restore).  Cheap cache key for anything derived from residuals.
- ``epoch`` bumps only on events that make an in-flight optimistic solve
  *unsalvageable*: liveness changes (``fail_node``/``fail_link``/restores)
  and :meth:`restore` rollbacks.  Plain commits/releases do NOT bump it —
  an in-flight batch solved against a slightly older residual is still
  usable because every mapping is re-validated against the host residual
  before committing (the existing optimistic-concurrency hook).  ``epoch``
  is monotone and never restored from a snapshot, so a stale in-flight
  solve can never be made to look fresh by a rollback.

Host view: ``residual_graph()`` hands out float32 ``cap``/``bw``/``lat``
with liveness applied, the exact arrays :meth:`ResidualState._full_view`
computes from the float64 truth.  That view is computed in full once and
otherwise patched in place: a commit or release rewrites the O(p) entries
it touched, a liveness change one node's row and column or one link's two
entries.  Every patched entry is recomputed from float64 by the full
view's own formula, never accumulated in float32, so the view stays
bit-identical to a full rebuild.  A frozen handout never changes afterwards
(a dispatched batch reconstructs on it): it takes its own copy of ``cap``
and shares ``bw``/``lat`` copy-on-write, as a snapshot does for
:meth:`restore`.  The state holds each such holder by weak reference, and a
write copies an n x n array only while a holder of that very array is still
alive; one dropped before the write (the depth-1 pipeline's dispatched
graph, dropped at ``finalize`` before the commits) costs nothing.  The
device mirror keeps the handout of its last full upload alive for as long
as the mirror lives, because JAX may read the host array after
``jnp.asarray`` returns (on the CPU it aliases a large array zero-copy, so a
write in place would change the device tensor under its scatter-add
deltas): the first write after a full upload copies, once.

Float32 drift: the device tensors are updated incrementally in float32
while the host accumulates in float64, so after many commits they can
differ from a fresh ``float32(host)`` round-trip by a few ulps.  That is
safe by construction — the DP only *proposes* mappings; host-side
``validate_mapping`` against the float64 truth gates every commit, and a
proposal the drifted tensors made infeasible-looking merely costs a
conflict re-solve.  Liveness changes drop the device cache entirely (they
rewrite ``lat`` semantics, not just magnitudes).
"""
from __future__ import annotations

import itertools
import time
import weakref

import numpy as np

from .graph import INF, ResourceGraph
from .problem import finite_lat
from ..obs import trace as obs_trace


def _pow2_pad(arr: np.ndarray) -> np.ndarray:
    """Zero-pad a 1-d scatter operand to the next power-of-two length.

    Padding appends index 0 / value 0.0 pairs, which are no-ops under
    scatter-*add* — the point is shape stability: delta sizes vary per
    commit, and an unpadded update would jit-compile one executable per
    distinct length instead of O(log n) bucketed ones."""
    k = len(arr)
    m = 1 << max(0, int(k - 1).bit_length())
    if m == k:
        return arr
    return np.concatenate([arr, np.zeros(m - k, arr.dtype)])


class _SnapshotView(dict):
    """A snapshot's ``{cap, bw, lat}``: a dict that takes a weak reference,
    so the state can tell whether the snapshot still holds the arrays."""


class ResidualState:
    """Residual capacity/bandwidth of one resource network: float64 host
    truth + a float32 host view patched in place + lazily synchronized
    float32 device tensors + staleness fences."""

    def __init__(self, base: ResourceGraph, tracer=None):
        self.base = base
        self.tracer = tracer if tracer is not None else obs_trace.NULL
        n = base.n
        self.cap = base.cap.astype(np.float64).copy()
        self.bw = base.bw.astype(np.float64).copy()
        self.node_up = np.ones(n, bool)
        self.link_up = np.isfinite(base.lat) & ~np.eye(n, dtype=bool)
        self.version = 0  # bumps on every host mutation
        self.epoch = 0  # bumps only when in-flight solves become invalid
        self._dev: dict | None = None  # {"cap","bw","lat"} jnp tensors
        self._upload: ResourceGraph | None = None  # host source of _dev
        self._node_delta: dict[int, float] = {}  # node -> pending cap delta
        self._edge_delta: dict[tuple, float] = {}  # (u,v) -> pending bw delta
        # telemetry (repro.obs registry reads these): how often the device
        # mirror paid a full O(n^2) upload vs an O(delta) scatter-add, and
        # the wall clock of every sync; how often the host view was
        # computed in full, the entries patched in place, the view arrays
        # copied on write because a live handout shared them, and the first
        # writes to a shared array whose holders had all died (no copy)
        self.sync_stats = {"full_uploads": 0, "delta_syncs": 0,
                           "invalidations": 0, "sync_ms": 0.0,
                           "full_views": 0, "patched_entries": 0,
                           "cow_copies": 0, "cow_avoided": 0}
        # call site -> [calls, ms] of residual_graph()
        self.rebuilds: dict[str, list] = {}
        self._view = self._full_view()
        # view array name -> (holder, array) weak references: a frozen
        # handout or snapshot that shares that array, dead ones pruned
        self._holders: dict[str, list] = {"bw": [], "lat": []}
        self.sync_stats["full_views"] += 1

    # -- host truth ---------------------------------------------------------

    def _full_view(self) -> dict:
        """Float32 ``{cap, bw, lat}`` of the residual network computed in
        full from the float64 truth: committed capacity subtracted, failed
        nodes/links removed (cap 0 / bw 0 / lat INF), ``lat``'s diagonal 0.
        The in-place view equals it bit for bit."""
        up2 = self.node_up[:, None] & self.node_up[None, :]
        alive = self.link_up & up2
        cap = np.where(self.node_up, self.cap, 0.0).astype(np.float32)
        bw = np.where(alive, self.bw, 0.0).astype(np.float32)
        lat = np.where(alive, self.base.lat, INF).astype(np.float32)
        np.fill_diagonal(lat, 0.0)
        return dict(cap=cap, bw=bw, lat=lat)

    def _hold(self, holder) -> None:
        """Register ``holder`` (a frozen handout or a snapshot's view) as
        sharing the view's current ``bw``/``lat`` for as long as it lives."""
        ref = weakref.ref(holder)
        for name in ("bw", "lat"):
            held = [e for e in self._holders[name] if e[0]() is not None]
            held.append((ref, weakref.ref(self._view[name])))
            self._holders[name] = held

    def _writable(self, name: str) -> np.ndarray:
        """View array ``name``, first copied if a frozen handout or a
        snapshot that shares it is still alive (copy-on-write keeps both
        unchanged).  A first write to a shared array whose holders have all
        died takes it in place and counts ``cow_avoided``."""
        arr = self._view[name]
        held = self._holders[name]
        shared = [h for h, a in held if a() is arr]
        if shared:
            if any(h() is not None for h in shared):
                arr = self._view[name] = arr.copy()
                self.sync_stats["cow_copies"] += 1
            else:
                self.sync_stats["cow_avoided"] += 1
            self._holders[name] = [e for e in held if e[0]() is not None]
        return arr

    def _patch(self, nodes, us, vs, *, lat: bool = False) -> None:
        """Recompute the view's ``cap[nodes]`` and ``bw[us, vs]`` (and
        ``lat[us, vs]``) from the float64 truth by :meth:`_full_view`'s
        formulas: O(entries), nothing accumulated in float32."""
        if len(nodes):
            nodes = np.asarray(nodes, np.intp)
            self._view["cap"][nodes] = np.where(
                self.node_up[nodes], self.cap[nodes], 0.0)
        if len(us):
            us, vs = np.asarray(us, np.intp), np.asarray(vs, np.intp)
            alive = self.link_up[us, vs] & self.node_up[us] & self.node_up[vs]
            self._writable("bw")[us, vs] = np.where(
                alive, self.bw[us, vs], 0.0)
            if lat:
                self._writable("lat")[us, vs] = np.where(
                    us == vs, 0.0, np.where(alive, self.base.lat[us, vs], INF))
        self.sync_stats["patched_entries"] += len(nodes) + len(us) * (1 + lat)

    def residual_graph(self, site: str = "other", *,
                       frozen: bool = True) -> ResourceGraph:
        """The network the next solve sees: committed capacity subtracted,
        failed nodes/links removed (cap 0 / bw 0 / lat INF).  ``site``
        names the caller in the rebuild counters.

        A frozen graph never changes afterwards: it owns a copy of ``cap``
        and shares ``bw``/``lat`` with the view, which the next write to
        either copies only if this graph is still alive then.  Drop it as
        soon as it is no longer read.  ``frozen=False`` returns the live
        view instead, which the next mutation changes: for a read that is
        done before then (validation between commits); it registers
        nothing and copies nothing."""
        t0 = time.perf_counter()
        with self.tracer.span("rebuild", track="residual", site=site):
            view = self._view
            cap = view["cap"].copy() if frozen else view["cap"]
            rg = ResourceGraph(cap, view["bw"], view["lat"])
            if frozen:
                self._hold(rg)
        c = self.rebuilds.setdefault(site, [0, 0.0])
        c[0] += 1
        c[1] += 1e3 * (time.perf_counter() - t0)
        return rg

    def apply_load(self, node_load: dict, edge_load: dict, sign: float) -> None:
        """Commit (``sign=-1``) or release (``sign=+1``) a ticket's loads.

        Host arrays and the host view update immediately; the device mirror
        accumulates the delta and applies it as one scatter-add at the next
        dispatch."""
        for v, c in node_load.items():
            d = sign * c
            self.cap[v] += d
            if self._dev is not None and self.node_up[v]:
                self._node_delta[v] = self._node_delta.get(v, 0.0) + d
        for (u, v), b in edge_load.items():
            d = sign * b
            self.bw[u, v] += d
            if self._dev is not None and self.link_up[u, v]:
                key = (u, v)
                self._edge_delta[key] = self._edge_delta.get(key, 0.0) + d
        self._patch(list(node_load), [u for u, _ in edge_load],
                    [v for _, v in edge_load])
        self.version += 1

    # -- liveness (drops the device cache: lat changes shape of the problem)

    def set_node_up(self, v: int, up: bool) -> None:
        self.node_up[v] = up
        n = self.base.n
        row, ids = np.full(n, v), np.arange(n)
        self._patch([v], np.concatenate([row, ids]),
                    np.concatenate([ids, row]), lat=True)
        self._invalidate()

    def set_link_up(self, u: int, v: int, up: bool) -> None:
        self.link_up[u, v] = self.link_up[v, u] = up
        self._patch([], [u, v], [v, u], lat=True)
        self._invalidate()

    def _invalidate(self) -> None:
        """Liveness changed or state rolled back: fence out in-flight solves
        and force a full device re-upload on the next dispatch."""
        self.version += 1
        self.epoch += 1
        self._dev = self._upload = None
        self._node_delta.clear()
        self._edge_delta.clear()
        self.sync_stats["invalidations"] += 1

    # -- snapshot / restore -------------------------------------------------

    def snapshot(self) -> dict:
        """Copy of the float64 truth and liveness, with the view: its own
        ``cap``, ``bw``/``lat`` shared copy-on-write for as long as the
        snapshot's ``"view"`` lives."""
        view = _SnapshotView(self._view, cap=self._view["cap"].copy())
        self._hold(view)
        return {
            "cap": self.cap.copy(),
            "bw": self.bw.copy(),
            "node_up": self.node_up.copy(),
            "link_up": self.link_up.copy(),
            "view": view,
        }

    def restore(self, snap: dict) -> None:
        """Roll back to a snapshot.  ``epoch`` advances (never rewinds): any
        solve dispatched between snapshot and restore stays stale forever.
        The view takes the snapshot's arrays, which the snapshot keeps
        sharing (it stays reusable): its registration as their holder
        lasts as long as it lives."""
        self.cap = snap["cap"].copy()
        self.bw = snap["bw"].copy()
        self.node_up = snap["node_up"].copy()
        self.link_up = snap["link_up"].copy()
        self._view = dict(snap["view"], cap=snap["view"]["cap"].copy())
        self._invalidate()

    # -- device mirror ------------------------------------------------------

    def warm_deltas(self) -> None:
        """Pre-compile the pow2-bucketed scatter-add executables by pushing
        zero-valued (no-op) deltas of every bucket size through the update
        path.  Residuals, ``version`` and ``epoch`` are untouched — this
        exists so the first *real* commits after a cold start don't pay the
        per-shape jit (the same reason :meth:`OnlinePlacer.warmup` exists
        for the DP buckets)."""
        self.device_tensors()  # materialize the mirror (full-upload path)
        n = self.base.n
        limit = min(4 * n, n * (n - 1))
        pairs = list(itertools.islice(
            ((u, v) for u in range(n) for v in range(n) if u != v), limit))
        k = 1
        while k <= limit:
            self._node_delta = {v: 0.0 for v in range(min(k, n))}
            self._edge_delta = {pairs[i]: 0.0 for i in range(k)}
            self.device_tensors()
            k *= 2

    def device_tensors(self) -> dict:
        """Float32 jnp ``{cap, bw, lat}`` of the current residual network.

        Full upload when the cache was dropped (construction, liveness
        change, restore); otherwise one scatter-add per tensor over the
        pending commit/release deltas.  A full upload keeps its frozen
        handout for as long as the mirror lives, so the view is copied
        before its first write instead of changing the uploaded array."""
        t0 = time.perf_counter()
        with self.tracer.span("sync", track="residual"):
            dev = self._sync()
        self.sync_stats["sync_ms"] += 1e3 * (time.perf_counter() - t0)
        return dev

    def _sync(self) -> dict:
        import jax.numpy as jnp  # deferred: numpy-only backends never touch jax

        if self._dev is None:
            rg = self._upload = self.residual_graph("upload")
            self._dev = dict(
                cap=jnp.asarray(rg.cap),
                bw=jnp.asarray(rg.bw),
                lat=jnp.asarray(finite_lat(rg)),
            )
            self._node_delta.clear()
            self._edge_delta.clear()
            self.sync_stats["full_uploads"] += 1
            return self._dev
        if self._node_delta or self._edge_delta:
            self.sync_stats["delta_syncs"] += 1
        # delta lengths are padded to the next power of two (pad entries add
        # 0.0 at index 0 — a no-op under scatter-ADD), so the jitted update
        # compiles O(log n) shape specializations, not one per delta size
        if self._node_delta:
            idx = _pow2_pad(np.fromiter(
                self._node_delta, np.int32, len(self._node_delta)))
            val = _pow2_pad(np.fromiter(
                self._node_delta.values(), np.float32, len(self._node_delta)))
            self._dev["cap"] = self._dev["cap"].at[jnp.asarray(idx)].add(
                jnp.asarray(val))
            self._node_delta.clear()
        if self._edge_delta:
            us = _pow2_pad(
                np.array([u for u, _ in self._edge_delta], np.int32))
            vs = _pow2_pad(
                np.array([v for _, v in self._edge_delta], np.int32))
            val = _pow2_pad(np.fromiter(
                self._edge_delta.values(), np.float32, len(self._edge_delta)))
            self._dev["bw"] = self._dev["bw"].at[
                jnp.asarray(us), jnp.asarray(vs)].add(jnp.asarray(val))
            self._edge_delta.clear()
        return self._dev
