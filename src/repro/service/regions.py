"""Decentralized regional control plane: one recursive broker over sharded
queues, gossiped shares, compacted region-local solves, and bounded
two-phase commit for dataflows that span regions.

The paper argues mapping should be computable *without* aggregating global
network state at one node.  The centralized :class:`ControlPlane` holds a
global view; :class:`RegionalControlPlane` shards it, recursively::

    RegionalControlPlane (levels=L, branching=b)
      ids: its own network's — but only at its boundary (cut gateways)
      owns: a cut ledger, spanning queues, one GossipBus over its R regions
        |
        +-- region r in 0..R-1: a CompactedView of r's nodes, and under it
            a plane in the view's LOCAL ids [0, n_r): a region-local
            ControlPlane at levels=1 (the leaves), else a broker of this
            same class with levels L-1 — regions of regions

- ``ControlPlane(rg, regions=R)`` (or ``region_of=``) builds the one-level
  broker: the network is partitioned into R balanced, BFS-grown regions
  (:func:`partition_regions`, or a caller-pinned ``region_of``) and each
  region owns a full centralized :class:`ControlPlane` over its
  **compacted** subgraph, so every piece of regional state — residual
  arrays, liveness masks, tickets, DP state, kernel tiles — is sized
  ``n_r``, not the global ``n``.  ``R = 1`` is the *bit-identical*
  degenerate case: the identity view translates by returning its inputs.
  ``ControlPlane(rg, levels=L, branching=b)`` nests brokers so that no
  component holds more than O(branching + n_leaf) state.
- regions never read each other's live accounting.  A
  :class:`~repro.service.gossip.GossipBus` per broker spreads versioned
  per-tenant committed-share / residual / gateway-occupancy estimates
  among its R regions (``R * fanout`` messages per round), and each
  region's fair-share drain runs against *local truth + gossiped
  estimates*, with an enclosing broker's estimate pushed down through
  ``pump(extra_committed=...)``.  Stale estimates can only skew drain
  order; admission always validates against the region's own residual.
- a request whose endpoints lie in different regions is decomposed over a
  **region chain** of the quotient graph (regions joined by alive cut
  links), cut at one edge per hop (:func:`split_dataflow_chain`) into one
  gateway-pinned segment per region.  The broker tries at most
  ``max_cut_attempts`` (splits, cut-edges) candidates — over the fewest-hop
  chain (``chain_k == 1``) or raced over Yen k-shortest chains priced by
  congestion (``chain_k > 1``) — and places each candidate with ONE
  bounded two-phase commit: each segment's phase-1 reserve is its region's
  ``broker_admit`` (a leaf reserves on its placer; a broker region may
  split the segment again at its own cuts), then the cut bandwidth, then
  commit — or every reservation rolls back.  Only a leaf accepts a
  preempting reserve, and only for a candidate's *last* segment.  A
  candidate costs at most ``2 * len(chain) + 2`` messages.

Each broker holds ids of exactly two kinds: its own boundary gateways (the
cut ledger) and opaque region handles.  Every spanning reservation is a
:class:`SpanPart` — ``(region, handle, local segment, view version)`` —
and cross-region (cut) links belong to no region: their bandwidth is the
broker's own ledger.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
import itertools
import math
from typing import Optional

import numpy as np

from ..core import engine
from ..core.compact import CompactedView
from ..core.graph import INF, DataflowPath, ResourceGraph
from ..core.online import Ticket
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .controlplane import ControlPlane, Request, TenantState
from .gossip import GossipBus
from .policy import FairSharePolicy, TenantConfig, fairness_summary

_EPS = 1e-9


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------


def partition_regions(rg: ResourceGraph, R: int, *, seed: int = 0) -> np.ndarray:
    """Balanced BFS partition: node -> region id in ``[0, R)``.

    R seed nodes are drawn (seeded rng), then regions grow breadth-first
    one node per sweep — sizes differ by at most one.  A region whose
    frontier is exhausted (disconnected remainder) grabs the
    lowest-indexed unassigned node, so every node is always assigned.
    Deterministic for a fixed (graph, R, seed).  Every region is
    guaranteed non-empty (R is clamped to ``n``; each region owns its
    seed node) — an empty region raises instead of failing downstream in
    view construction.
    """
    n = rg.n
    if n == 0:
        raise ValueError("cannot partition an empty resource graph (n=0)")
    R = max(1, min(int(R), n))
    if R == 1:
        return np.zeros(n, np.int64)
    rng = np.random.default_rng(seed)
    assign = np.full(n, -1, np.int64)
    seeds = np.sort(rng.choice(n, size=R, replace=False))
    frontiers: list[collections.deque] = []
    for r, s in enumerate(seeds):
        assign[s] = r
        frontiers.append(collections.deque(rg.neighbors(int(s))))
    unassigned = n - R
    while unassigned:
        for r in range(R):
            node = None
            while frontiers[r]:
                cand = int(frontiers[r].popleft())
                if assign[cand] < 0:
                    node = cand
                    break
            if node is None:
                rem = np.nonzero(assign < 0)[0]
                if rem.size == 0:
                    break
                node = int(rem[0])
            assign[node] = r
            frontiers[r].extend(rg.neighbors(node))
            unassigned -= 1
            if not unassigned:
                break
    counts = np.bincount(assign, minlength=R)
    if counts.min() == 0:  # unreachable with seeded growth; fail loudly
        raise ValueError(
            f"partition produced an empty region (n={n}, R={R}, "
            f"sizes={counts.tolist()}); use fewer regions"
        )
    return assign


def validate_region_of(rg: ResourceGraph, region_of) -> np.ndarray:
    """Validate a caller-supplied node -> region assignment: one id per
    node, contiguous region ids ``0..R-1``, every region non-empty.
    Raises a clear ``ValueError`` instead of letting view construction
    fail downstream."""
    assign = np.asarray(region_of, np.int64)
    if assign.shape != (rg.n,):
        raise ValueError(
            f"region_of must map every node: expected shape ({rg.n},), "
            f"got {assign.shape}"
        )
    if rg.n == 0:
        raise ValueError("cannot shard an empty resource graph (n=0)")
    if assign.min() < 0:
        raise ValueError("region_of contains negative region ids")
    R = int(assign.max()) + 1
    counts = np.bincount(assign, minlength=R)
    empty = np.nonzero(counts == 0)[0]
    if empty.size:
        raise ValueError(
            f"region_of leaves region(s) {empty.tolist()} empty "
            f"(region ids must be contiguous 0..{R - 1} and every region "
            "must own at least one node); merge or renumber the regions"
        )
    return assign


def resolve_nesting(levels, branching, regions, detected_leaves=None):
    """Fail-fast resolution of the nesting kwargs into
    ``(levels, branching, leaf_regions)``.  Contradictory combinations
    raise with a clear message instead of silently building some other
    plane (mirrors the flat plane's ``regions=`` vs ``region_of=``
    contradiction check)."""
    levels = int(levels)
    if levels < 1:
        raise ValueError(f"levels={levels} must be >= 1")
    leaves = detected_leaves
    if regions is not None:
        if leaves is not None and int(regions) != leaves:
            raise ValueError(
                f"regions={regions} contradicts region_of, which defines "
                f"{leaves} regions"
            )
        leaves = int(regions)
    if levels == 1:
        if branching is not None and leaves is not None \
                and int(branching) != leaves:
            raise ValueError(
                f"branching={branching} contradicts {leaves} leaf regions "
                "at levels=1 (a 1-level plane has branching == regions)"
            )
        if leaves is None:
            leaves = int(branching) if branching is not None else 2
        return levels, leaves, leaves
    if branching is None:
        if leaves is None:
            branching = 2
        else:
            branching = round(leaves ** (1.0 / levels))
            if branching**levels != leaves:
                raise ValueError(
                    f"regions={leaves} is not a perfect levels={levels} "
                    "power; pass branching= explicitly (leaf regions = "
                    "branching ** levels)"
                )
    branching = int(branching)
    if branching < 1:
        raise ValueError(f"branching={branching} must be >= 1")
    if leaves is not None and branching**levels != leaves:
        raise ValueError(
            f"regions={leaves} contradicts levels={levels} x "
            f"branching={branching} (expected {branching ** levels} "
            "leaf regions)"
        )
    return levels, branching, branching**levels


def region_subgraph(rg: ResourceGraph, assign: np.ndarray, r: int) -> ResourceGraph:
    """The subgraph region ``r`` owns, in the *global* id space:
    out-of-region nodes keep their ids but lose all capacity and links.

    Superseded on the control-plane path by
    :class:`~repro.core.compact.CompactedView` (which drops foreign rows
    entirely instead of masking them, so solves run at ``n_r``); kept as
    the masking reference the compacted substrate is equivalence-tested
    against."""
    mine = assign == r
    pair = mine[:, None] & mine[None, :]
    cap = np.where(mine, rg.cap, 0.0).astype(np.float32)
    bw = np.where(pair, rg.bw, 0.0).astype(np.float32)
    lat = np.where(pair, rg.lat, INF).astype(np.float32)
    np.fill_diagonal(lat, 0.0)
    return ResourceGraph(cap, bw, lat)


def cut_edges(rg: ResourceGraph, assign: np.ndarray) -> list[tuple[int, int]]:
    """Directed physical links crossing a region boundary."""
    return [
        (u, v) for (u, v) in rg.edges() if assign[u] != assign[v]
    ]


def split_dataflow_chain(
    df: DataflowPath,
    splits,
    gates,
) -> list[DataflowPath]:
    """Decompose ``df`` along a region chain: cut at dataflow edges
    ``splits[0] <= ... <= splits[m-1]``, hop ``i`` crossing the cut link
    ``gates[i] = (u_i, v_i)``.  Segment ``i`` holds dataflow nodes
    ``splits[i-1]+1 .. splits[i]`` (sentinels -1 / p-1), pinned from the
    inbound head gateway ``v_{i-1}`` (``df.src`` for the first) to the
    outbound tail gateway ``u_i`` (``df.dst`` for the last); cut ``i``
    carries ``breq[splits[i]]``.

    Segments are pinned to the gateways through **ghost endpoints**: a
    zero-compute dataflow node at the in/out gateway, joined to the
    segment's real boundary node by an edge carrying the cut dataflow
    edge's bandwidth — so the in-region transport from wherever the
    boundary node is placed to the gateway is reserved honestly, and no
    dataflow node is forced to sit *at* a gateway.  Equal consecutive
    splits make the region between them a pure **transit** region: no
    real dataflow node, just the two ghost gateway endpoints and the one
    carried edge (a single ghost node when both gateways coincide).
    Transit is what admits a short dataflow between non-adjacent regions
    (e.g. p = 2 across a 3-region chain).  Endpoints stay in global ids —
    the broker compacts each segment into its region's local space at
    reserve time.
    """
    p = df.p
    m = len(splits)
    bounds = [-1] + list(splits) + [p - 1]
    segs = []
    for i in range(len(bounds) - 1):
        lo, hi = bounds[i] + 1, bounds[i + 1]
        if lo > hi:  # transit: carries dataflow edge splits[i-1] only
            u, v = int(gates[i - 1][1]), int(gates[i][0])
            carried = float(df.breq[splits[i - 1]])
            if u == v:
                segs.append(DataflowPath(
                    np.zeros(1, np.float32), np.zeros(0, np.float32), u, v))
            else:
                segs.append(DataflowPath(
                    np.zeros(2, np.float32),
                    np.asarray([carried], np.float32), u, v))
            continue
        creq = list(np.asarray(df.creq[lo:hi + 1], np.float64))
        breq = list(np.asarray(df.breq[lo:hi], np.float64))
        if i == 0:
            src = int(df.src)
        else:  # ghost at the inbound head gateway, carrying the cut edge
            src = int(gates[i - 1][1])
            creq = [0.0] + creq
            breq = [float(df.breq[splits[i - 1]])] + breq
        if i == m:
            dst = int(df.dst)
        else:  # ghost at the outbound tail gateway, carrying the cut edge
            dst = int(gates[i][0])
            creq = creq + [0.0]
            breq = breq + [float(df.breq[splits[i]])]
        segs.append(DataflowPath(
            np.asarray(creq, np.float32), np.asarray(breq, np.float32),
            src, dst,
        ))
    return segs


def split_dataflow(
    df: DataflowPath, s: int, u: int, v: int
) -> tuple[DataflowPath, DataflowPath]:
    """Single-cut decomposition at dataflow edge ``s`` across the cut
    link (u, v) — the chain of length 2 (see
    :func:`split_dataflow_chain`)."""
    a, b = split_dataflow_chain(df, [s], [(u, v)])
    return a, b


# ---------------------------------------------------------------------------
# spanning placements
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpanPart:
    """One reserved segment of a spanning placement: the owning region,
    the region's reservation handle (a leaf's ticket id, a broker
    region's rid), the *local-id* segment object the reservation holds
    (identity-checked by the invariants), and the region view's
    bijection version at reserve time — a part minted under
    an older generation than the view's current one is a churn survivor,
    and one minted under a newer-than-current version is a bug."""

    region: int
    tid: int
    seg: DataflowPath
    version: int


@dataclasses.dataclass(eq=False)
class SpanningTicket:
    """Composite handle for a cross-region placement: one reserved
    segment per region on the chain plus one cut-bandwidth reservation
    per hop.  ``parts`` hold (region, tid) pairs, not Ticket objects —
    region defrag re-keys tickets under stable tids, so the handle
    survives re-optimization."""

    rid: int
    req: Request
    parts: list[SpanPart]  # ordered along the region chain
    cuts: list[tuple[int, int]]  # global gateway pairs, one per hop
    cut_bws: list[float]
    splits: list[int]  # dataflow edge indices carried by the cuts

    @property
    def tenant(self) -> str:
        return self.req.tenant

    @property
    def klass(self) -> int:
        return self.req.klass

    @property
    def df(self) -> DataflowPath:
        return self.req.df

    @property
    def chain(self) -> list[int]:
        """The ordered region chain this placement spans."""
        return [p.region for p in self.parts]

    # single-cut convenience (the chain-of-2 common case)
    @property
    def cut(self) -> tuple[int, int]:
        return self.cuts[0]

    @property
    def cut_bw(self) -> float:
        return self.cut_bws[0]

    @property
    def split(self) -> int:
        return self.splits[0]


class RegionalControlPlane:
    """A broker over R regions, each a region-local :class:`ControlPlane`
    (``levels == 1``) or a broker of this class (``levels >= 2``).

    Mirrors the centralized :class:`ControlPlane` surface (register_tenant
    / submit / pump / release / fail_* / restore_* / defrag /
    committed_capacity / conservation / fairness_report / engine_stats /
    check_invariants / active_ids), so call sites are plane-agnostic, and
    exposes the reservation interface every region exposes to its broker
    (``broker_admit`` / ``broker_release`` / ``broker_uses_node`` /
    ``broker_uses_link`` / ``broker_holds`` and the ``on_broker_displace``
    hook), so brokers nest to any depth.  ``pump`` returns a mix of
    :class:`Ticket` (in one leaf, in that leaf's *local* ids — lift them
    through :meth:`owner_region` and ``plane.views[r]``) and
    :class:`SpanningTicket` handles; ``defrag`` returns one
    :class:`~repro.service.defrag.DefragResult` per leaf region — there is
    no global re-solve, by design.

    ``**solve_cfg`` (including ``cache_enabled`` / ``cache_size`` /
    ``max_correction_supersteps``) reaches every leaf placer, whose
    solution cache works on view-local signatures against its own residual
    truth, so no cross-region coherence is needed.
    """

    def __init__(
        self,
        rg: ResourceGraph,
        *,
        regions: Optional[int] = None,
        region_of=None,
        levels: Optional[int] = None,
        branching: Optional[int] = None,
        policy: Optional[FairSharePolicy] = None,
        micro_batch: int = 32,
        max_attempts: int = 8,
        preempt: bool = True,
        preempt_budget: Optional[float] = None,
        pipeline_depth: int = 1,
        method: str = "leastcost_jax",
        use_kernel: bool = False,
        fanout: int = 2,
        gossip_period: int = 1,
        max_cut_attempts: int = 4,
        chain_k: int = 2,
        congestion_weight: float = 1.0,
        max_cum_attempts: Optional[int] = None,
        seed: int = 0,
        tracer=None,
        **solve_cfg,
    ):
        self.base = rg
        if branching is not None and levels in (None, 1):
            raise ValueError(
                f"branching={branching} requires a hierarchical plane "
                "(levels >= 2); the flat plane takes regions= or region_of="
            )
        assign = None if region_of is None else validate_region_of(rg, region_of)
        self.levels, self.branching, _ = resolve_nesting(
            1 if levels is None else levels, branching, regions,
            detected_leaves=None if assign is None else int(assign.max()) + 1,
        )
        # leaves per region; region_of maps node -> direct region
        sub = self.branching ** (self.levels - 1)
        if assign is not None:
            self.region_of = assign // sub
        else:
            self.region_of = partition_regions(rg, self.branching, seed=seed)
        self.R = int(self.region_of.max()) + 1
        self.policy = policy or FairSharePolicy()
        self.micro_batch = int(micro_batch)
        self.max_attempts = int(max_attempts)
        self.preempt = bool(preempt)
        self.preempt_budget = preempt_budget
        self.method = method
        self.max_cut_attempts = int(max_cut_attempts)
        # chain_k > 1 races k-shortest region chains under the load-aware
        # cost; chain_k == 1 is the single fewest-hop chain
        self.chain_k = max(1, int(chain_k))
        self.congestion_weight = float(congestion_weight)
        # lifetime attempt budget across displacement episodes: a request
        # ping-ponging between admission and displacement resets its
        # per-episode attempts but never this one (livelock backstop)
        self.max_cum_attempts = (
            4 * self.max_attempts if max_cum_attempts is None
            else int(max_cum_attempts)
        )
        self.gossip_period = max(1, int(gossip_period))
        # each region gets a scoped view of this tracer ("r{r}/" prefixes),
        # so flow ids and track names nest the way the planes do
        self.tracer = tracer if tracer is not None else obs_trace.NULL
        # the compacted solve substrate: one bijection per region
        self.views = [
            CompactedView.from_assign(rg, self.region_of, r)
            for r in range(self.R)
        ]
        kw = dict(policy=self.policy, micro_batch=micro_batch,
                  max_attempts=max_attempts, preempt=preempt,
                  preempt_budget=preempt_budget,
                  pipeline_depth=pipeline_depth, method=method,
                  use_kernel=use_kernel, **solve_cfg)
        if self.levels == 1:
            self.regions: list = [
                ControlPlane(rg, view=view, tracer=self.tracer.scoped(f"r{r}"),
                             **kw)
                for r, view in enumerate(self.views)
            ]
        else:
            self.regions = []
            for r, view in enumerate(self.views):
                child = RegionalControlPlane(
                    view.graph(), levels=self.levels - 1, regions=sub,
                    region_of=(None if assign is None
                               else assign[view.nodes] - r * sub),
                    fanout=fanout, gossip_period=gossip_period,
                    max_cut_attempts=max_cut_attempts, chain_k=chain_k,
                    congestion_weight=congestion_weight,
                    max_cum_attempts=max_cum_attempts,
                    seed=seed + 1000 * (r + 1),
                    tracer=self.tracer.scoped(f"r{r}"), **kw,
                )
                # a leaf churn's invalidate() propagates up to this
                # level's view (and an invalidation here cascades down)
                for cv in child.views:
                    view.adopt(cv)
                self.regions.append(child)
        for r, child in enumerate(self.regions):
            child.on_broker_displace = (
                lambda h, old, r=r: self._child_displaced(r, h, old))
            child.on_drop = lambda req, r=r: self._forget_local(r, req)
        self.leaf_regions = len(self.leaf_planes())
        self.bus = GossipBus(self.R, fanout=fanout,
                             seed=seed + (1 if self.levels == 1 else 7))
        self.node_up = np.ones(rg.n, bool)

        # cut-edge bandwidth ledger: cut links belong to no region (they
        # are outside every compacted submatrix), so this ledger is their
        # only accounting, reserved/released by the broker's 2PC
        self.cut_base: dict[tuple[int, int], float] = {}
        self.cut_residual: dict[tuple[int, int], float] = {}
        self.cut_link_up: dict[tuple[int, int], bool] = {}
        self._cut_by_pair: dict[tuple[int, int], list[tuple[int, int]]] = {}
        self._gateways_of: dict[int, list[int]] = {}
        for (u, v) in cut_edges(rg, self.region_of):
            self.cut_base[(u, v)] = float(rg.bw[u, v])
            self.cut_residual[(u, v)] = float(rg.bw[u, v])
            self.cut_link_up[(u, v)] = True
            self._cut_by_pair.setdefault(
                (int(self.region_of[u]), int(self.region_of[v])), []
            ).append((u, v))
            gws = self._gateways_of.setdefault(int(self.region_of[u]), [])
            if u not in gws:
                gws.append(u)
        for gws in self._gateways_of.values():
            gws.sort()

        # spanning-request bookkeeping (the broker's ledger)
        self.span_tenants: dict[str, TenantState] = {}
        self._span_q: list[dict[str, collections.deque]] = [
            {} for _ in range(self.R)
        ]
        self._span_active: dict[int, SpanningTicket] = {}
        self._part_of: dict[tuple[int, int], int] = {}  # (region, h) -> rid
        # rid space over both in-region and spanning requests
        self._rid = itertools.count()
        self._local: dict[int, tuple[int, int]] = {}  # rid -> (region, lrid)
        self._grid_of: dict[tuple[int, int], int] = {}  # (region, lrid) -> rid
        self._pumps = 0
        self._twopc_msgs = 0
        # while a churn call (fail_node/fail_link) is reconciling, spanning
        # placements torn down by in-region rescue preemptions collect here
        # so the churn return contract covers them too
        self._churn_collector: Optional[list] = None
        # reservations held by an enclosing broker's 2PC (broker_admit):
        # their lifecycle belongs to that broker — a displacement fires
        # on_broker_displace instead of requeueing here, and they are not
        # caller-visible active requests
        self._broker_held: set[int] = set()
        self.on_broker_displace = None  # enclosing broker: (rid, old) -> None
        self.on_drop = None  # called with each Request this plane drops
        self.span_stats = {
            "attempts": 0, "admitted": 0, "dropped": 0,
            "displaced": 0, "no_cut": 0,
            "multi_hop": 0,  # admitted over chains of >= 3 regions
            "max_chain": 0,  # longest admitted region chain
            "broker_local": 0,  # enclosing-broker reservations in one region
            "rerouted": 0,  # admitted via a non-fewest-hop chain
            "livelock_dropped": 0,  # dropped by the cumulative budget
            "max_req_attempts": 0,  # highest lifetime attempts on one req
        }

    # -- registration / submission ------------------------------------------

    def register_tenant(
        self, name: str, *, weight: float = 1.0,
        budget: Optional[float] = None,
    ) -> TenantConfig:
        if name in self.span_tenants:
            raise ValueError(f"tenant {name!r} already registered")
        cfg = TenantConfig(name, weight=weight, budget=budget)
        for child in self.regions:
            child.register_tenant(name, weight=weight, budget=budget)
        self.span_tenants[name] = TenantState(cfg)
        for q in self._span_q:
            q[name] = collections.deque()
        return cfg

    def submit(self, tenant: str, df: DataflowPath, *, klass: int = 0) -> int:
        """Queue a request with its *home* (source) region: one whose
        endpoints lie in one region is compacted into that region's local
        id space and submitted there (a broker region may still split it
        across ITS regions); one crossing this broker's cuts queues on the
        home region's broker side and is placed by 2PC at pump time.
        Returns an rid of this plane."""
        st = self.span_tenants[tenant]  # KeyError for unregistered
        rid = next(self._rid)
        ra = int(self.region_of[df.src])
        rb = int(self.region_of[df.dst])
        if ra == rb:
            lrid = self.regions[ra].submit(
                tenant, self.views[ra].compact_df(df), klass=klass
            )
            self._local[rid] = (ra, lrid)
            self._grid_of[(ra, lrid)] = rid
        else:
            st.submitted += 1
            ControlPlane._enqueue(
                self._span_q[ra][tenant], Request(rid, tenant, df, klass=klass)
            )
            if self.tracer.enabled:
                self.tracer.flow_begin(
                    rid, "submit", tenant=tenant, klass=klass,
                    spanning=True, home=ra,
                )
        return rid

    # -- live accounting -----------------------------------------------------

    def committed_capacity(self) -> dict[str, float]:
        """Per-tenant compute held anywhere under this broker, from the
        leaf placers' tickets (spanning segments included)."""
        held = {t: 0.0 for t in self.span_tenants}
        for child in self.regions:
            for t, c in child.held_capacity().items():
                held[t] = held.get(t, 0.0) + c
        return held

    held_capacity = committed_capacity

    def residual_capacity(self) -> float:
        """Summed live residual node capacity of every leaf region (the
        scalar this plane publishes into its enclosing broker's gossip)."""
        return float(sum(c.residual_capacity() for c in self.regions))

    def queued_demand(self) -> dict[str, float]:
        out = {t: 0.0 for t in self.span_tenants}
        for child in self.regions:
            for t, c in child.queued_demand().items():
                out[t] = out.get(t, 0.0) + c
        for q in self._span_q:
            for t, dq in q.items():
                out[t] += sum(r.creq_sum for r in dq)
        return out

    def owner_region(self, ticket: Ticket) -> Optional[int]:
        """The region holding ``ticket`` (by object identity — tids are
        per-leaf counters and collide across regions).  Use it to pick the
        ``plane.views[r]`` that lifts an in-region handle to this plane's
        ids."""
        for r, child in enumerate(self.regions):
            if child.ticket_live(ticket):
                return r
        return None

    def active_ids(self) -> list[int]:
        """Rids of active requests in every region + spanning.  Reservations
        held for an enclosing broker are excluded — they are segments of a
        composite that broker accounts for."""
        out = [
            self._grid_of[(r, h)]
            for r, child in enumerate(self.regions)
            for h in child.active_ids()
            if (r, h) in self._grid_of
        ]
        out += [rid for rid in self._span_active if rid not in self._broker_held]
        return sorted(out)

    def ticket_live(self, t) -> bool:
        """Is a handle returned by :meth:`pump` still standing?  (A later
        round — or an enclosing broker's 2PC — may have displaced it.)"""
        if self._span_active.get(getattr(t, "rid", -1)) is t:
            return True
        return any(child.ticket_live(t) for child in self.regions)

    def conservation(self) -> dict[str, int]:
        """The regions' ledgers + this broker's spanning ledger.  ``ok``
        iff every submitted request is in exactly one state *summed over
        regions*; a reservation held for an enclosing broker counts in
        both ledgers, and each balances on its own."""
        agg = {"submitted": 0, "queued": 0, "in_flight": 0, "active": 0,
               "released": 0, "dropped": 0}
        for child in self.regions:
            led = child.conservation()
            for k in agg:
                agg[k] += led[k]
        agg["submitted"] += sum(
            st.submitted for st in self.span_tenants.values())
        agg["queued"] += sum(
            len(dq) for q in self._span_q for dq in q.values())
        agg["active"] += len(self._span_active)
        agg["released"] += sum(
            st.released for st in self.span_tenants.values())
        agg["dropped"] += sum(
            st.dropped for st in self.span_tenants.values())
        agg["ok"] = agg["submitted"] == (
            agg["queued"] + agg["in_flight"] + agg["active"]
            + agg["released"] + agg["dropped"]
        )
        return agg

    # -- gossip --------------------------------------------------------------

    def node_occupancy(self, v: int) -> float:
        """Compute occupancy of node ``v`` in [0, 1] (1.0 when down), read
        from the leaf placer that holds it."""
        if not self.node_up[v]:
            return 1.0
        r = int(self.region_of[v])
        return self.regions[r].node_occupancy(int(self.views[r].to_local(v)))

    def _publish(self, r: int) -> None:
        """Publish region r's aggregated accounting into this broker's bus,
        with the occupancy of its gateways as the per-cut congestion
        estimate."""
        child = self.regions[r]
        queued = child.queued_demand()
        for t, dq in self._span_q[r].items():
            queued[t] = queued.get(t, 0.0) + sum(x.creq_sum for x in dq)
        self.bus.publish(
            r, child.held_capacity(), queued, child.residual_capacity(),
            congestion={u: self.node_occupancy(u)
                        for u in self._gateways_of.get(r, ())},
        )

    # -- admission -----------------------------------------------------------

    def pump(self, *, rounds: int = 1, extra_committed=None) -> list:
        """One decentralized drain round per ``rounds``: publish + gossip
        share estimates, drain every region's queues under estimated
        global fair shares, then place queued spanning requests by bounded
        2PC.  Returns the still-live admitted handles.

        ``extra_committed`` is an enclosing broker's estimate of per-tenant
        holdings *outside this plane* (the gossip downlink); it folds into
        every region's drain as gossiped sibling estimates do — advisory
        for drain order, never capacity."""
        admitted: list = []
        spanned: list[SpanningTicket] = []
        for _ in range(int(rounds)):
            self._pumps += 1
            for r in range(self.R):
                self._publish(r)
            if self.R > 1 and self._pumps % self.gossip_period == 0:
                with self.tracer.span("gossip.round", track="gossip",
                                      cat="gossip", round=self._pumps):
                    self.bus.tick()
            for r, child in enumerate(self.regions):
                extra: dict[str, float] = dict(extra_committed or {})
                if self.R > 1:
                    # gossiped estimate of remote holdings
                    for t, c in self.bus.remote_committed(r).items():
                        extra[t] = extra.get(t, 0.0) + c
                if self.R > 1 and self.levels == 1:
                    # plus what a leaf holds for brokers: spanning segments
                    # are placer tickets but not the leaf's own requests,
                    # so its accounting cannot see them (a broker region's
                    # accounting counts its reservations itself)
                    own = child.committed_capacity()
                    for t, c in child.held_capacity().items():
                        diff = c - own.get(t, 0.0)
                        if diff > _EPS:
                            extra[t] = extra.get(t, 0.0) + diff
                admitted += child.pump(rounds=1, extra_committed=extra or None)
            spanned += self._pump_spanning(extra_committed)
        live = [t for t in admitted if self.ticket_live(t)]
        live += [s for s in spanned if s.rid in self._span_active]
        return live

    def flush(self) -> list:
        """Commit every leaf's in-flight pipeline windows (barrier); see
        :meth:`ControlPlane.flush`.  The 2PC needs no flush of its own: it
        reserves host-side, and an in-flight batch that loses capacity to
        a reservation re-solves its conflicts at commit."""
        admitted: list = []
        for child in self.regions:
            admitted += child.flush()
        return [t for t in admitted if self.ticket_live(t)]

    def warmup(self, *, max_batch: Optional[int] = None, p: int = 5) -> int:
        """Pre-compile every leaf's jit buckets (their ``n_r`` differ)."""
        return max(
            (c.warmup(max_batch=max_batch, p=p) for c in self.regions),
            default=0,
        )

    def _pump_spanning(self, extra_committed=None) -> list[SpanningTicket]:
        if self.R <= 1:
            return []
        out: list[SpanningTicket] = []
        cfgs = {t: st.cfg for t, st in self.span_tenants.items()}
        for r in range(self.R):
            queues = self._span_q[r]
            if not any(queues.values()):
                continue
            committed = self.regions[r].held_capacity()
            for t, c in self.bus.remote_committed(r).items():
                if t in committed:
                    committed[t] += c
            for t, c in (extra_committed or {}).items():
                if t in committed:
                    committed[t] += c
            picked = self.policy.select(
                cfgs, queues, committed, self.micro_batch
            )
            # pop every selected head BEFORE placing: a 2PC attempt may
            # displace another spanning request to the front of one of
            # these very queues, which must not disturb the drain order
            for req in picked:
                q = queues[req.tenant]
                assert q[0] is req, "policy must select queue heads in order"
                q.popleft()
            for req in picked:
                q = queues[req.tenant]
                st = self._try_place_spanning(req)
                if st is not None:
                    self.span_tenants[req.tenant].admitted += 1
                    if self.tracer.enabled:
                        self.tracer.flow_point(
                            req.rid, "admit", chain=len(st.parts))
                    out.append(st)
                else:
                    req.attempts += 1
                    req.cum_attempts += 1
                    self.span_stats["max_req_attempts"] = max(
                        self.span_stats["max_req_attempts"], req.cum_attempts)
                    exhausted = req.attempts >= self.max_attempts
                    livelocked = req.cum_attempts >= self.max_cum_attempts
                    if exhausted or livelocked:
                        self.span_tenants[req.tenant].dropped += 1
                        self.span_stats["dropped"] += 1
                        if livelocked and not exhausted:
                            self.span_stats["livelock_dropped"] += 1
                        if self.tracer.enabled:
                            self.tracer.flow_end(
                                req.rid, "drop", outcome="dropped",
                                attempts=req.attempts,
                                cum_attempts=req.cum_attempts,
                            )
                        if self.on_drop is not None:
                            self.on_drop(req)
                    else:
                        ControlPlane._enqueue(q, req, front_of_class=True)
        return out

    def _requeue_or_livelock_drop(self, st: SpanningTicket) -> None:
        """Requeue a displaced spanning request at its home region — or
        drop it when its *cumulative* attempt budget is spent.  The
        per-episode ``attempts`` resets (displacement is not the
        request's fault) but ``cum_attempts`` never does: a request
        ping-ponging between a saturated chain and displacement meets
        ``max_cum_attempts`` instead of livelocking forever."""
        st.req.attempts = 0
        st.req.cum_attempts += 1
        self.span_stats["max_req_attempts"] = max(
            self.span_stats["max_req_attempts"], st.req.cum_attempts)
        if st.req.cum_attempts >= self.max_cum_attempts:
            self.span_tenants[st.tenant].dropped += 1
            self.span_stats["dropped"] += 1
            self.span_stats["livelock_dropped"] += 1
            if self.tracer.enabled:
                self.tracer.flow_end(
                    st.rid, "drop", outcome="livelock",
                    cum_attempts=st.req.cum_attempts,
                )
            if self.on_drop is not None:
                self.on_drop(st.req)
            return
        home = int(self.region_of[st.df.src])
        ControlPlane._enqueue(
            self._span_q[home][st.tenant], st.req, front_of_class=True
        )

    # -- reservation interface to an enclosing broker -------------------------

    def broker_admit(self, tenant: str, df: DataflowPath, *,
                     klass: int = 0) -> Optional[int]:
        """Synchronous, abortable phase-1 reserve for an enclosing
        broker's 2PC: place ``df`` (in THIS plane's ids) now — in one
        region, or across this plane's own cuts (the recursion that lets a
        segment split again one level down).

        Returns a handle for :meth:`broker_release`, or None (nothing
        reserved).  The reservation is a spanning entry of this plane's
        ledger, so conservation and invariants hold at every level; if
        churn or preemption here later displaces it,
        ``on_broker_displace`` fires instead of a local requeue.  Never
        escalates to preemption: only a leaf does that."""
        st = self.span_tenants[tenant]  # KeyError for unregistered
        rid = next(self._rid)
        req = Request(rid, tenant, df, klass=klass)
        ra = int(self.region_of[df.src])
        rb = int(self.region_of[df.dst])
        if ra == rb:
            lseg = self.views[ra].compact_df(df)
            h = self.regions[ra].broker_admit(tenant, lseg, klass=klass)
            if h is None:
                return None
            self.span_stats["broker_local"] += 1
            span = SpanningTicket(
                rid=rid, req=req,
                parts=[SpanPart(ra, h, lseg, self.views[ra].version)],
                cuts=[], cut_bws=[], splits=[],
            )
            self._span_active[rid] = span
            self._part_of[(ra, h)] = rid
        else:
            span = self._try_place_spanning(req)
            if span is None:
                return None
        st.submitted += 1
        st.admitted += 1
        self._broker_held.add(rid)
        return rid

    def broker_release(self, rid: int, *, abort: bool = False) -> list[Ticket]:
        """Release (or phase-1 abort; both count as released here) a
        :meth:`broker_admit` reservation; returns the leaf tickets it
        freed.  Idempotent: a reservation this plane already displaced
        (and reported via ``on_broker_displace``) releases nothing."""
        if rid not in self._broker_held:
            return []
        self._broker_held.discard(rid)
        st = self._span_active.pop(rid)
        released = self._teardown_span(st)
        self.span_tenants[st.tenant].released += 1
        return released

    def broker_holds(self, rid: int, seg: DataflowPath) -> bool:
        """Is ``rid`` a live reservation of exactly ``seg``?"""
        st = self._span_active.get(rid)
        return rid in self._broker_held and st is not None and st.df is seg

    def broker_uses_node(self, rid: int, v: int) -> bool:
        """Does a reservation touch node ``v`` (this plane's ids)?  Used by
        the enclosing broker to scope churn displacement."""
        st = self._span_active.get(rid)
        return st is not None and self._span_uses_node(st, int(v))

    def broker_uses_link(self, rid: int, u: int, v: int) -> bool:
        st = self._span_active.get(rid)
        if st is None:
            return False
        u, v = int(u), int(v)
        return any(c in ((u, v), (v, u)) for c in st.cuts) or \
            self._span_uses_link(st, u, v)

    # -- region chains: quotient graph and congestion-aware k-shortest --------

    def _cut_alive(self, u: int, v: int) -> bool:
        return (
            self.cut_link_up.get((u, v), False)
            and bool(self.node_up[u]) and bool(self.node_up[v])
        )

    def _quotient_adjacency(self) -> dict[int, dict[int, float]]:
        """The quotient graph of children under the currently-alive cut
        edges: ``adj[r1][r2]`` = min latency among alive (r1 -> r2) cuts."""
        adj: dict[int, dict[int, float]] = {}
        for (r1, r2), edges in self._cut_by_pair.items():
            lats = [
                float(self.base.lat[e]) for e in edges if self._cut_alive(*e)
            ]
            if lats:
                adj.setdefault(r1, {})[r2] = min(lats)
        return adj

    def _region_chain(self, ra: int, rb: int) -> Optional[list[int]]:
        """Fewest-hop child chain ``ra -> ... -> rb`` over the quotient
        graph (ties by summed min cut latency, then child ids — fully
        deterministic).  None when the quotient graph is partitioned."""
        adj = self._quotient_adjacency()
        best: dict[int, tuple[int, float]] = {ra: (0, 0.0)}
        heap: list[tuple[int, float, tuple[int, ...]]] = [(0, 0.0, (ra,))]
        while heap:
            hops, lat, path = heapq.heappop(heap)
            r = path[-1]
            if r == rb:
                return list(path)
            if (hops, lat) > best.get(r, (hops, lat)):
                continue  # stale heap entry
            for nb in sorted(adj.get(r, {})):
                if nb in path:
                    continue
                cand = (hops + 1, lat + adj[r][nb])
                if nb not in best or cand < best[nb]:
                    best[nb] = cand
                    heapq.heappush(heap, (*cand, path + (nb,)))
        return None

    # -- congestion-aware k-shortest chains -----------------------------------

    def _edge_congestion(self, e: tuple[int, int],
                         occ_view: dict[int, float]) -> float:
        """Congestion estimate for one cut edge: this broker's own ledger
        utilization of the cut, plus the gossiped occupancy of both
        gateway endpoints.  The ledger term is exact (2PC-maintained);
        the occupancy terms may be arbitrarily stale — they only ever
        rank chains, never admit over capacity."""
        base = self.cut_base[e]
        util = 1.0 - self.cut_residual[e] / base if base > 0 else 0.0
        u, v = e
        return max(0.0, util) + occ_view.get(u, 0.0) + occ_view.get(v, 0.0)

    def _edge_cost(self, e: tuple[int, int],
                   occ_view: dict[int, float]) -> float:
        """Load-aware chain metric: ``lat * (1 + w * congestion)``.  With
        ``congestion_weight == 0`` this degenerates to pure latency."""
        lat = float(self.base.lat[e])
        w = self.congestion_weight
        if w <= 0.0:
            return lat
        return lat * (1.0 + w * self._edge_congestion(e, occ_view))

    def _cost_adjacency(
        self, occ_view: dict[int, float]
    ) -> dict[int, dict[int, float]]:
        """Quotient graph under the load-aware metric: ``adj[r1][r2]`` =
        min :meth:`_edge_cost` among alive (r1 -> r2) cuts."""
        adj: dict[int, dict[int, float]] = {}
        for (r1, r2), edges in self._cut_by_pair.items():
            costs = [
                self._edge_cost(e, occ_view)
                for e in edges if self._cut_alive(*e)
            ]
            if costs:
                adj.setdefault(r1, {})[r2] = min(costs)
        return adj

    @staticmethod
    def _dijkstra_chain(adj, ra: int, rb: int, banned_nodes=(),
                        banned_edges=()) -> Optional[tuple[float, list[int]]]:
        """Deterministic least-cost loopless path ``ra -> rb`` over a cost
        adjacency (ties by hops then child ids).  ``banned_nodes`` /
        ``banned_edges`` support Yen spur searches."""
        banned_nodes = set(banned_nodes)
        banned_edges = set(banned_edges)
        best: dict[int, tuple[float, int]] = {ra: (0.0, 0)}
        heap: list[tuple[float, int, tuple[int, ...]]] = [(0.0, 0, (ra,))]
        while heap:
            cost, hops, path = heapq.heappop(heap)
            r = path[-1]
            if r == rb:
                return cost, list(path)
            if (cost, hops) > best.get(r, (cost, hops)):
                continue  # stale heap entry
            for nb in sorted(adj.get(r, {})):
                if nb in path or nb in banned_nodes or (r, nb) in banned_edges:
                    continue
                cand = (cost + adj[r][nb], hops + 1)
                if nb not in best or cand < best[nb]:
                    best[nb] = cand
                    heapq.heappush(heap, (*cand, path + (nb,)))
        return None

    def _region_chains(self, ra: int, rb: int,
                       occ_view: dict[int, float]) -> list[list[int]]:
        """Up to ``chain_k`` loopless region chains ``ra -> rb`` by Yen's
        algorithm under the load-aware edge cost, cheapest first.  Chains
        through hot gateways cost more, so a saturated fewest-hop chain
        sorts behind a longer cold bypass *before* any 2PC probes it.
        ``chain_k == 1`` planes take the fewest-hop :meth:`_region_chain`
        instead."""
        adj = self._cost_adjacency(occ_view)
        first = self._dijkstra_chain(adj, ra, rb)
        if first is None:
            return []
        found: list[tuple[float, list[int]]] = [first]
        seen = {tuple(first[1])}
        frontier: list[tuple[float, int, tuple[int, ...]]] = []
        while len(found) < self.chain_k:
            _, prev = found[-1]
            for i in range(len(prev) - 1):
                root = prev[:i + 1]
                spur_bans = {
                    (p[i], p[i + 1]) for _, p in found
                    if len(p) > i + 1 and p[:i + 1] == root
                }
                spur = self._dijkstra_chain(
                    adj, root[-1], rb, banned_nodes=root[:-1],
                    banned_edges=spur_bans,
                )
                if spur is None:
                    continue
                scost, spath = spur
                rcost = sum(adj[root[j]][root[j + 1]] for j in range(i))
                path = tuple(root[:-1] + spath)
                if path not in seen:
                    seen.add(path)
                    heapq.heappush(
                        frontier, (rcost + scost, len(path) - 1, path))
            if not frontier:
                break
            cost, _, path = heapq.heappop(frontier)
            found.append((cost, list(path)))
        return [p for _, p in found]

    def _race_candidates(self, df: DataflowPath, chains: list[list[int]],
                         occ_view: dict[int, float]) -> list:
        """Round-robin interleave of ``(chain, splits, gates)`` candidates
        across the k chains, cheapest chain first, with gates per hop
        ordered by the same load-aware cost.  The total is capped at
        ``max_cut_attempts`` — racing chains never widens the 2PC probe
        budget beyond the single-chain broker's."""
        budget = self.max_cut_attempts

        def key(e):
            return (self._edge_cost(e, occ_view), float(self.base.lat[e]), e)

        per = [
            collections.deque(
                self._candidate_chains(df, ch, limit=budget, edge_key=key))
            for ch in chains
        ]
        out = []
        while len(out) < budget and any(per):
            for ch, dq in zip(chains, per):
                if dq:
                    splits, gates = dq.popleft()
                    out.append((ch, splits, gates))
                    if len(out) >= budget:
                        break
        return out

    def _chain_feasible(self, df: DataflowPath, splits, gates) -> bool:
        """Cut-bandwidth screen for one candidate.  Ghost gateway
        endpoints (see :func:`split_dataflow_chain`) remove every
        structural pinning constraint — whether a segment can actually
        route from its gateway is the child solve's decision."""
        for s, e in zip(splits, gates):
            if self.cut_residual[e] + _EPS < float(df.breq[s]):
                return False
        return True

    def _candidate_chains(self, df: DataflowPath, chain: list[int], *,
                          limit: Optional[int] = None,
                          edge_key=None) -> list:
        """Up to ``limit`` (default ``max_cut_attempts``) (splits,
        cut-edges) candidates for a child chain: split combinations
        (non-decreasing — repeats make transit regions) ordered by compute
        balance across the segments, cut edges per hop by ``edge_key``
        (default link latency; hop order lexicographic)."""
        limit = self.max_cut_attempts if limit is None else max(1, int(limit))
        m = len(chain) - 1
        p = df.p
        edge_lists = []
        for (r1, r2) in zip(chain[:-1], chain[1:]):
            edges = [
                e for e in self._cut_by_pair.get((r1, r2), ())
                if self._cut_alive(*e)
            ]
            if not edges:
                return []
            edges.sort(key=edge_key if edge_key is not None
                       else lambda e: float(self.base.lat[e]))
            edge_lists.append(edges)
        prefix = np.concatenate([[0.0], np.cumsum(df.creq.astype(np.float64))])
        target = float(prefix[-1]) / (m + 1)

        def balance(splits):
            bounds = (-1,) + splits + (p - 1,)
            return sum(
                abs(float(prefix[bounds[i + 1] + 1] - prefix[bounds[i] + 1])
                    - target)
                for i in range(m + 1)
            )

        # bounded search: the exact combination space C(p+m-2, m) is only
        # enumerated while it is small; long dataflows over long chains
        # restrict each cut's candidate positions to a window around its
        # balanced quantile (where balance() is minimized anyway), and a
        # hard islice cap bounds the scoring work outright.  nsmallest
        # then keeps a pool sized so even an adversarial run of
        # infeasible splits cannot starve the max_cut_attempts quota.
        positions = range(p - 1)
        if math.comb(p - 1 + m - 1, m) > 20_000:
            target_pos = {
                min(max(int(np.searchsorted(
                    prefix, float(prefix[-1]) * i / (m + 1))) + d, 0), p - 2)
                for i in range(1, m + 1)
                for d in range(-4, 5)
            }
            positions = sorted(target_pos)
        pool = max(32, 8 * self.max_cut_attempts)
        combos = heapq.nsmallest(
            pool,
            itertools.islice(
                itertools.combinations_with_replacement(positions, m),
                50_000),
            key=lambda s: (balance(s), s),
        )
        out = []
        for splits in combos:
            for gates in itertools.product(*edge_lists):
                if not self._chain_feasible(df, splits, gates):
                    continue
                out.append((splits, gates))
                if len(out) >= limit:
                    return out
        return out

    # -- two-phase commit over the chain -------------------------------------

    def _attempt_candidate(self, req: Request, chain: list[int], splits,
                           gates, can_preempt: bool) -> Optional[SpanningTicket]:
        """One bounded 2PC over every segment of one candidate.

        Each segment is compacted into its region's ids and reserved by
        that region's ``broker_admit`` in chain order (a broker region may
        run its own chain split and 2PC inside).  With ``can_preempt`` (a
        leaf level only) at most ONE failed reserve may be retried with
        budgeted preemption, and only as the candidate's *last* missing
        reservation while every sibling is held — so victims are displaced
        only by an admission that commits.  A candidate that cannot
        complete aborts every reservation it took.  Message cost per
        candidate is at most ``2 * len(chain) + 2`` (prepare/commit per
        segment, plus the nack + preemptive re-prepare of the one blocker).
        """
        df = req.df
        segs = split_dataflow_chain(df, splits, gates)
        local: dict[int, DataflowPath] = {}
        held: dict[int, int] = {}
        failed: list[int] = []
        tr = self.tracer
        for i, seg in enumerate(segs):
            r = chain[i]
            local[i] = self.views[r].compact_df(seg)
            self._twopc_msgs += 1  # prepare segment i
            with tr.span("2pc.reserve", track="2pc", cat="2pc", region=r):
                h = self.regions[r].broker_admit(
                    req.tenant, local[i], klass=req.klass)
            if h is None:
                self._twopc_msgs += 1  # nack i
                if tr.enabled:
                    tr.flow_point(req.rid, "2pc.nack", region=r)
                failed.append(i)
                if not can_preempt or len(failed) > 1:
                    break  # candidate dead: >1 blocker can't be rescued
            else:
                held[i] = h
                if tr.enabled:
                    tr.flow_point(req.rid, "2pc.reserve", region=r)
        if len(failed) == 1 and can_preempt and len(held) == len(segs) - 1:
            i = failed[0]
            r = chain[i]
            self._twopc_msgs += 1  # prepare i, preemptive retry (last)
            with tr.span("2pc.reserve.preempt", track="2pc", cat="2pc",
                         region=r):
                h = self.regions[r].broker_admit(
                    req.tenant, local[i], klass=req.klass, preempt=True)
            if h is None:
                self._twopc_msgs += 1  # nack i
                if tr.enabled:
                    tr.flow_point(req.rid, "2pc.nack", region=r,
                                  preempting=True)
            else:
                held[i] = h
                failed = []
                if tr.enabled:
                    tr.flow_point(req.rid, "2pc.reserve", region=r,
                                  preempting=True)
        ok = not failed and len(held) == len(segs) and all(
            self.cut_residual[e] + _EPS >= float(df.breq[s])
            for s, e in zip(splits, gates)
        )
        if not ok:
            for i in sorted(held):
                self._twopc_msgs += 1  # abort i
                if tr.enabled:
                    tr.flow_point(req.rid, "2pc.abort", region=chain[i])
                self.regions[chain[i]].broker_release(held[i], abort=True)
            return None
        self._twopc_msgs += len(segs)  # commit every segment
        if tr.enabled:
            tr.flow_point(req.rid, "2pc.commit", chain=len(segs))
        cut_bws = [float(df.breq[s]) for s in splits]
        for e, b in zip(gates, cut_bws):
            self.cut_residual[e] -= b
        st = SpanningTicket(
            rid=req.rid, req=req,
            parts=[SpanPart(chain[i], held[i], local[i],
                            self.views[chain[i]].version)
                   for i in range(len(segs))],
            cuts=[tuple(e) for e in gates], cut_bws=cut_bws,
            splits=list(splits),
        )
        self._span_active[req.rid] = st
        for part in st.parts:
            self._part_of[(part.region, part.tid)] = req.rid
        self.span_stats["admitted"] += 1
        if len(chain) >= 3:
            self.span_stats["multi_hop"] += 1
        self.span_stats["max_chain"] = max(
            self.span_stats["max_chain"], len(chain))
        return st

    def _try_place_spanning(self, req: Request) -> Optional[SpanningTicket]:
        """Chain selection + bounded 2PC over the cut candidates.  This is
        the single accounting site for spanning placement attempts —
        ``span_stats["attempts"]`` counts every entry here (from the pump
        drain AND from an enclosing broker's ``broker_admit``), ``admitted``
        every 2PC commit, so ``attempts >= admitted`` holds by
        construction (see :meth:`check_invariants`).

        ``chain_k == 1``: the single fewest-hop region chain over the
        quotient graph, with latency-ordered gate candidates.

        ``chain_k > 1``: Yen k-shortest chains under the load-aware cost
        (the broker's own cut-ledger utilization + gossiped gateway
        occupancy), raced round-robin under the same ``max_cut_attempts``
        2PC budget — when the fewest-hop chain runs hot, a cold bypass
        chain gets probed before the request burns its whole budget."""
        df = req.df
        self.span_stats["attempts"] += 1
        ra = int(self.region_of[df.src])
        rb = int(self.region_of[df.dst])
        can_preempt = self.levels == 1 and self.preempt and req.klass > 0
        if self.chain_k <= 1:
            chain = self._region_chain(ra, rb)
            raced = [] if chain is None else [
                (chain, splits, gates)
                for splits, gates in self._candidate_chains(df, chain)
            ]
        else:
            occ = self.bus.congestion_view(ra)
            raced = self._race_candidates(
                df, self._region_chains(ra, rb, occ), occ)
        if not raced:
            self.span_stats["no_cut"] += 1
            return None
        for (chain, splits, gates) in raced:
            st = self._attempt_candidate(req, chain, splits, gates,
                                         can_preempt)
            if st is not None:
                if chain != self._region_chain(ra, rb):
                    self.span_stats["rerouted"] += 1
                return st
        return None

    # -- teardown / displacement ---------------------------------------------

    def _forget_local(self, r: int, req: Request) -> None:
        """Region r dropped one of its requests: forget its rid mapping
        (the maps must not grow without bound) and pass the drop up as a
        Request of this plane — its rid, its endpoints in this plane's
        ids."""
        rid = self._grid_of.pop((r, req.rid), None)
        if rid is not None:
            self._local.pop(rid, None)
            if self.on_drop is not None:
                self.on_drop(dataclasses.replace(
                    req, rid=rid, df=self.views[r].uncompact_df(req.df)))

    def _teardown_span(self, st: SpanningTicket,
                       skip: Optional[tuple[int, int]] = None) -> list[Ticket]:
        """Release every still-held reservation of a spanning placement
        (``skip`` names a (region, handle) already gone) and return the cut
        bandwidth; returns the leaf tickets freed.  Region releases are
        idempotent, so the teardown always completes for *all* siblings
        and never leaks a partial reservation."""
        old: list[Ticket] = []
        for part in st.parts:
            self._part_of.pop((part.region, part.tid), None)
            if skip is not None and (part.region, part.tid) == skip:
                continue
            old += self.regions[part.region].broker_release(part.tid)
        for e, b in zip(st.cuts, st.cut_bws):
            self.cut_residual[e] += b
        return old

    def _hand_up(self, rid: int, st: SpanningTicket, old: list) -> bool:
        """After a displacement teardown: a reservation held for an
        enclosing broker ends here and is reported up (True); anything
        else stays this plane's to requeue (False)."""
        if rid not in self._broker_held:
            return False
        self._broker_held.discard(rid)
        self.span_tenants[st.tenant].released += 1
        if self.on_broker_displace is not None:
            self.on_broker_displace(rid, old)
        return True

    def _child_displaced(self, r: int, h: int, old: list) -> None:
        """Region r displaced (preemption or churn) reservation ``h`` of
        one of this broker's spanning placements; ``old`` lists the leaf
        tickets that went with it.  Tear the rest of the composite down
        and requeue it at its home region (or hand it further up).
        Idempotent: a second report of a torn-down span is a no-op."""
        rid = self._part_of.get((r, h))
        if rid is None:
            return  # not a spanning segment (or span already torn down)
        st = self._span_active.pop(rid, None)
        if st is None:
            self._part_of.pop((r, h), None)
            return
        # the displacement event was already counted once by the victim
        # segment's preemption/drop — siblings are bookkeeping
        old = old + self._teardown_span(st, skip=(r, h))
        self.span_stats["displaced"] += 1
        self.span_tenants[st.tenant].preempted += 1
        if self.tracer.enabled:
            self.tracer.flow_point(rid, "displaced", region=r)
        if not self._hand_up(rid, st, old):
            self._requeue_or_livelock_drop(st)
        if self._churn_collector is not None:
            # one level up, a churn call reports the leaf tickets it freed
            # (the centralized plane's contract); higher up, the spans
            self._churn_collector.extend(old if self.levels == 1 else [st])

    def _displace_spans(self, pred) -> list:
        """Tear down every active spanning placement matching ``pred`` and
        requeue its request at its home region (environment displacement
        is handled exactly like preemption: accounted, never dropped).
        Returns what the churn call reports: the freed leaf tickets at
        ``levels == 1``, the requeued spans above."""
        old: list[Ticket] = []
        requeue: list[SpanningTicket] = []
        for rid in [g for g, st in self._span_active.items() if pred(st)]:
            st = self._span_active.pop(rid)
            released = self._teardown_span(st)
            old += released
            self.span_stats["displaced"] += 1
            self.span_tenants[st.tenant].preempted += 1
            if self.tracer.enabled:
                self.tracer.flow_point(rid, "displaced", churn=True)
            if not self._hand_up(rid, st, released):
                requeue.append(st)
        # back-to-front so the batch keeps FIFO-within-class order in any
        # shared home queue (a cumulative-budget drop leaves its slot empty)
        for st in reversed(requeue):
            self._requeue_or_livelock_drop(st)
        return old if self.levels == 1 else requeue

    # -- release / churn ------------------------------------------------------

    def release(self, rid: int) -> None:
        if rid in self._broker_held:
            raise KeyError(
                f"rid {rid} is a parent-held broker reservation; it is "
                "released through broker_release by the plane that holds it"
            )
        st = self._span_active.pop(rid, None)
        if st is not None:
            # guarded teardown (tolerates a sibling whose region already
            # dropped its reservation); the request-level release is
            # accounted once, by this ledger
            self._teardown_span(st)
            self.span_tenants[st.tenant].released += 1
            if self.tracer.enabled:
                self.tracer.flow_end(rid, "release", outcome="released")
            return
        r, lrid = self._local[rid]
        self.regions[r].release(lrid)  # raises if not active (caller bug)
        del self._local[rid]
        del self._grid_of[(r, lrid)]

    def _span_uses_node(self, st: SpanningTicket, v: int) -> bool:
        """Does the placement touch node ``v`` — as a gateway of any hop,
        or anywhere inside a region's segment (asked of the region, in its
        ids)?"""
        if any(v in c for c in st.cuts):
            return True
        for part in st.parts:
            view = self.views[part.region]
            if view.contains(v) and self.regions[part.region].broker_uses_node(
                    part.tid, int(view.to_local(v))):
                return True
        return False

    def _span_uses_link(self, st: SpanningTicket, u: int, v: int) -> bool:
        """Does a segment of the placement ride the in-region link (u, v)?"""
        for part in st.parts:
            view = self.views[part.region]
            if view.contains(u) and view.contains(v) and \
                    self.regions[part.region].broker_uses_link(
                        part.tid, int(view.to_local(u)),
                        int(view.to_local(v))):
                return True
        return False

    def _churn_call(self, fn) -> tuple[list, list]:
        """Run a region churn operation collecting any spanning placements
        its rescue preemptions displace, so the ``(alive, requeued)``
        return covers every handle the event invalidated."""
        self._churn_collector = collected = []
        try:
            alive, requeued = fn()
        finally:
            self._churn_collector = None
        return alive, requeued + collected

    def fail_node(self, v: int) -> tuple[list, list]:
        """Take node ``v`` down.  Spanning placements touching it (as a
        gateway or anywhere inside a segment) are displaced back to their
        queues first, then the owning region re-maps its own work in its
        local ids (its view is invalidated; the bump propagates to the
        enclosing views).  Same ``(alive, requeued)`` contract as the
        centralized plane; ``requeued`` also covers spanning placements
        displaced by rescue preemptions during the re-map."""
        v = int(v)
        self.node_up[v] = False
        requeued_span = self._displace_spans(
            lambda st: self._span_uses_node(st, v)
        )
        r = int(self.region_of[v])
        alive, requeued = self._churn_call(
            lambda: self.regions[r].fail_node(int(self.views[r].to_local(v)))
        )
        return alive, requeued + requeued_span

    def fail_link(self, u: int, v: int) -> tuple[list, list]:
        """Take a (symmetric) link down: an in-region link fails through
        the owning region (in its ids) once the spanning placements riding
        it have left through this broker; a *cut* link degrades the
        quotient graph — every spanning placement riding it is displaced
        and requeued, and chains re-route around it on the next pump
        (healed by ``restore_link``)."""
        u, v = int(u), int(v)
        if self.region_of[u] == self.region_of[v]:
            requeued_span = self._displace_spans(
                lambda st: self._span_uses_link(st, u, v)
            )
            r = int(self.region_of[u])
            view = self.views[r]
            alive, requeued = self._churn_call(
                lambda: self.regions[r].fail_link(
                    int(view.to_local(u)), int(view.to_local(v)))
            )
            return alive, requeued + requeued_span
        for e in ((u, v), (v, u)):
            if e in self.cut_link_up:
                self.cut_link_up[e] = False
        requeued_span = self._displace_spans(
            lambda st: any(c in ((u, v), (v, u)) for c in st.cuts)
        )
        return [], requeued_span

    def restore_node(self, v: int) -> None:
        v = int(v)
        self.node_up[v] = True
        r = int(self.region_of[v])
        self.regions[r].restore_node(int(self.views[r].to_local(v)))

    def restore_link(self, u: int, v: int) -> None:
        u, v = int(u), int(v)
        if self.region_of[u] == self.region_of[v]:
            r = int(self.region_of[u])
            view = self.views[r]
            self.regions[r].restore_link(
                int(view.to_local(u)), int(view.to_local(v)))
            return
        for e in ((u, v), (v, u)):
            if e in self.cut_link_up:
                self.cut_link_up[e] = bool(np.isfinite(self.base.lat[e]))

    # -- defragmentation ------------------------------------------------------

    def defrag(self, *, max_extras: Optional[int] = None) -> list:
        """Leaf-local re-optimization — there is deliberately no global
        re-solve (that would be the centralized plane again).  Spanning
        segments are standing tickets with pinned gateways, so each leaf
        may re-pack them locally; tids (and thus spanning handles) are
        preserved.  Returns one DefragResult per leaf, depth-first."""
        return [cp.defrag(max_extras=max_extras)
                for _, cp in self.leaf_planes()]

    # -- reporting / invariants ----------------------------------------------

    def leaf_planes(self) -> list:
        """Every leaf region's (view from this plane's ids, ControlPlane),
        depth-first — nested bijections composed into one, for
        cross-level write-through checks and reporting."""
        return [
            (view if cv is None else view.compose(cv), cp)
            for view, child in zip(self.views, self.regions)
            for cv, cp in child.leaf_planes()
        ]

    def _kernel_impl_counts(self) -> dict:
        """Per-backend solve counts summed over every leaf placer."""
        out: dict[str, int] = {}
        for child in self.regions:
            for k, v in child._kernel_impl_counts().items():
                out[k] = out.get(k, 0) + v
        return out

    def _solve_counts(self) -> tuple[int, int]:
        solves = n_sum = 0
        for child in self.regions:
            s, n = child._solve_counts()
            solves += s
            n_sum += n
        return solves, n_sum

    def engine_stats(self) -> engine.Stats:
        s = engine.Stats(method=self.method)
        for child in self.regions:
            cs = child.engine_stats()
            s.preemptions += cs.preemptions
            s.defrag_rounds += cs.defrag_rounds
            s.solve_ms += cs.solve_ms
            s.overhead_ms += cs.overhead_ms
            s.conflict_resolve_ms += cs.conflict_resolve_ms
            s.stale_batches += cs.stale_batches
            s.fallbacks += cs.fallbacks
            s.gossip_messages += cs.gossip_messages
            s.twopc_messages += cs.twopc_messages
        s.batch_size = self.micro_batch
        s.rounds = self.bus.rounds
        s.gossip_messages += self.bus.messages_sent
        s.twopc_messages += self._twopc_msgs
        s.messages_sent = s.gossip_messages + s.twopc_messages
        solves, n_sum = self._solve_counts()
        if solves:
            s.solve_n = round(n_sum / solves)
        # the non-additive fields fold as labeled consensus, not a sum:
        # the mix of backends that actually ran, never a silent drop
        s.kernel_impl = ControlPlane._consensus_impl(
            self._kernel_impl_counts())
        return s

    def metrics_registry(self) -> obs_metrics.MetricsRegistry:
        """One merged registry: every region's registry labeled
        ``plane=r{r}`` (label paths compose per level, e.g. ``r0/r1``),
        plus this broker's gossip / 2PC / spanning counters."""
        reg = obs_metrics.MetricsRegistry()
        for r, child in enumerate(self.regions):
            reg.merge(child.metrics_registry(), plane=f"r{r}")
        obs_metrics.absorb_gossip_stats(reg, self.bus.gossip_stats())
        obs_metrics.absorb_span_stats(reg, self.span_stats)
        reg.inc("twopc.messages", float(self._twopc_msgs))
        reg.gauge("plane.levels", float(self.levels))
        return reg

    def solve_size_report(self) -> dict:
        """The compute-locality story in numbers: the padded node
        dimension every leaf DP actually ran over, next to the global
        ``n`` an uncompacted plane would have paid."""
        leaves = self.leaf_planes()
        per = [{
            "region": i,
            "n_r": view.n_local,
            "solves": cp.placer.stats.solves,
            "mean_solve_n": cp.placer.stats.mean_solve_n,
        } for i, (view, cp) in enumerate(leaves)]
        solves = sum(p["solves"] for p in per)
        nsum = sum(cp.placer.stats.solve_n_sum for _, cp in leaves)
        return {
            "global_n": self.base.n,
            "regions": per,
            "solves": solves,
            "mean_solve_n": (nsum / solves) if solves else 0.0,
            "max_solve_n": max(
                (p["n_r"] for p in per if p["solves"]), default=0),
            "balanced_n_r": math.ceil(self.base.n / len(leaves)),
        }

    def resident_state_report(self, peers: int = 0) -> dict:
        """Max per-component resident state across the whole tree — the
        scaling metric nesting is graded on.  The broker holds its
        boundary id table (the distinct gateway ids of its cut ledger) plus
        one quotient entry and gossip record per region; each region adds
        one gossip record per sibling (``peers``) to its own state, down
        to the leaves' ``n_r``-sized solves.  One level is therefore
        O(boundary + R); nesting keeps every level at O(branching)."""
        gateway_ids = {v for e in self.cut_base for v in e}
        comps = [{
            "component": "broker",
            "id_table": len(gateway_ids),
            "peers": self.R + peers,
            "state": len(gateway_ids) + self.R + peers,
        }]
        for r, child in enumerate(self.regions):
            for c in child.resident_state_report(peers=self.R)["components"]:
                comps.append({**c, "component": f"region[{r}].{c['component']}"})
        return {
            "components": comps,
            "max_component_state": max(c["state"] for c in comps),
        }

    def coordination_report(self) -> dict:
        """The decentralization story in numbers: gossip volume/staleness
        and 2PC traffic next to the spanning admission outcomes and the
        compacted solve sizes; ``*_total`` sums every level."""
        es = self.engine_stats()
        return {
            "levels": self.levels,
            "regions": self.R,
            "branching": self.R,
            "leaf_regions": self.leaf_regions,
            "fanout": self.bus.fanout,
            "gossip_period": self.gossip_period,
            "gossip_rounds": self.bus.rounds,
            "gossip_messages": self.bus.messages_sent,
            "gossip_messages_per_round": (
                self.bus.messages_sent / max(self.bus.rounds, 1)
            ),
            "gossip_messages_total": es.gossip_messages,
            "max_staleness": self.bus.max_staleness(),
            "gossip": self.bus.gossip_stats(),
            "twopc_messages": self._twopc_msgs,
            "twopc_messages_total": es.twopc_messages,
            "spanning": dict(self.span_stats),
            "cut_edges": len(self.cut_base),
            "children": [] if self.levels == 1 else [
                c.coordination_report() for c in self.regions],
            "solve_size": self.solve_size_report(),
            "resident": self.resident_state_report(),
        }

    def fairness_report(self) -> dict:
        rep = fairness_summary(
            self.committed_capacity(),
            self.queued_demand(),
            {t: st.cfg.weight for t, st in self.span_tenants.items()},
        )
        rep["coordination"] = self.coordination_report()
        es = self.engine_stats()
        rep["timing"] = {
            "solve_ms": es.solve_ms,
            "overhead_ms": es.overhead_ms,
            "conflict_resolve_ms": es.conflict_resolve_ms,
        }
        return rep

    def check_invariants(self) -> None:
        """Every region's invariants (recursively), this broker's ledger,
        cut-bandwidth conservation, spanning-handle integrity (liveness,
        chain well-formedness, view versions), and the write-through
        conservation of the compacted substrate: the leaf residuals and
        ticket loads, lifted through the composed views, must re-assemble
        the base network exactly."""
        for child in self.regions:
            child.check_invariants()
        led = self.conservation()
        assert led["ok"], f"ticket conservation violated: {led}"
        # span accounting: attempts/admitted are counted at exactly one
        # site each (_try_place_spanning entry / 2PC commit), so the
        # counters nest strictly — a double-count on any path breaks this
        ss = self.span_stats
        assert 0 <= ss["admitted"] <= ss["attempts"], (
            f"span accounting violated: {ss}")
        assert ss["multi_hop"] <= ss["admitted"], (
            f"span accounting violated: {ss}")
        assert ss["rerouted"] <= ss["admitted"], (
            f"span accounting violated: {ss}")
        assert ss["livelock_dropped"] <= ss["dropped"] <= ss["attempts"], (
            f"span accounting violated: {ss}")
        assert len(self._span_active) <= ss["admitted"] + ss["broker_local"], (
            f"more active spans than admissions: {ss}")
        reserved = {e: 0.0 for e in self.cut_base}
        for st in self._span_active.values():
            for e, b in zip(st.cuts, st.cut_bws):
                reserved[e] += b
        for e, base_bw in self.cut_base.items():
            assert abs(self.cut_residual[e] + reserved[e] - base_bw) < 1e-6, (
                f"cut bandwidth conservation violated on {e}"
            )
            assert self.cut_residual[e] >= -1e-6, (
                f"negative cut residual on {e}"
            )
        for rid, st in self._span_active.items():
            assert len(st.parts) == len(st.cuts) + 1, (
                f"spanning rid {rid}: chain/cut arity mismatch"
            )
            assert list(st.splits) == sorted(st.splits), (
                f"spanning rid {rid}: splits not non-decreasing"
            )
            for i, (u, v) in enumerate(st.cuts):
                assert int(self.region_of[u]) == st.parts[i].region
                assert int(self.region_of[v]) == st.parts[i + 1].region
            for part in st.parts:
                assert self.regions[part.region].broker_holds(
                    part.tid, part.seg), (
                    f"spanning rid {rid} holds a stale or dead reservation "
                    f"in region {part.region}"
                )
                assert self._part_of.get((part.region, part.tid)) == rid
                assert part.version <= self.views[part.region].version, (
                    f"spanning rid {rid}: part minted under a future "
                    "view version"
                )
        # write-through conservation: re-assemble this plane's network
        # from the leaf state.  Node capacity must reconstruct exactly;
        # in-region bandwidth likewise; cut bandwidth is checked above (it
        # belongs to a broker, not to any region).
        n = self.base.n
        cap_res = np.zeros(n)
        cap_load = np.zeros(n)
        bw_res = np.zeros((n, n))
        bw_load = np.zeros((n, n))
        in_region = np.zeros((n, n), bool)
        for view, cp in self.leaf_planes():
            cap_res += view.uncompact_node_vec(cp.placer.cap)
            bw_res += view.uncompact_link_mat(cp.placer.bw)
            in_region |= view.uncompact_link_mat(
                np.ones((view.n_local, view.n_local), bool))
            for tk in cp.placer.tickets.values():
                for gv, c in view.uncompact_node_load(tk.node_load).items():
                    cap_load[gv] += c
                for (gu, gv), b in view.uncompact_edge_load(
                        tk.edge_load).items():
                    bw_load[gu, gv] += b
        assert np.allclose(cap_res + cap_load, self.base.cap, atol=1e-4), (
            "compacted-view write-through broke node-capacity conservation"
        )
        assert np.allclose(
            (bw_res + bw_load)[in_region], self.base.bw[in_region], atol=1e-4
        ), "compacted-view write-through broke link-bandwidth conservation"
