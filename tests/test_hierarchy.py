"""Nested regional brokers: the property suite of ``levels >= 2``.

The nesting claims, fuzz-enforced: nested planes keep every level's ticket
ledger, cut conservation and cross-level write-through intact under
adversarial interleavings; spanning decomposition recurses (a top-level
segment may split again inside its child); churn displacement chains
through ``on_broker_displace`` up the tree; gossip is tree-structured
(each level's bus carries at most ``branching`` aggregated records); and
no component's resident state scales with the global plane.
"""
import numpy as np
import pytest

from repro.core import DataflowPath, random_dataflow, region_tree
from repro.service import (
    ControlPlane,
    FairSharePolicy,
    RegionalControlPlane,
    resolve_nesting,
)

PYM = dict(method="leastcost_python")  # pure-python backend: fast, no jit


# ---------------------------------------------------------------------------
# topology generator
# ---------------------------------------------------------------------------


def test_region_tree_generator_shape():
    levels, b, k = 2, 3, 4
    rg, assign = region_tree(levels, b, k, seed=3)
    leaves = b**levels
    assert rg.n == leaves * k
    assert assign.shape == (rg.n,)
    # depth-first leaf numbering: contiguous node blocks per leaf
    np.testing.assert_array_equal(
        assign, np.repeat(np.arange(leaves), k))
    # leaves are fully meshed internally
    for leaf in range(leaves):
        base = leaf * k
        for i in range(k):
            for j in range(i + 1, k):
                assert rg.bw[base + i, base + j] > 0
    # grouping any contiguous block of b^(levels-1) leaves = one subtree;
    # siblings at every level are joined (the quotient graph is connected)
    sub = b ** (levels - 1)
    group_of = assign // sub
    cross = [
        (u, v) for (u, v) in rg.edges() if group_of[u] != group_of[v]
    ]
    assert cross, "top-level siblings must be joined by gateway links"
    # gateway links carry the scaled bandwidth and level-scaled latency
    for (u, v) in cross:
        assert rg.lat[u, v] == pytest.approx(5.0 * levels)
    # every pair of top-level groups is adjacent (all-to-all siblings)
    pairs = {(int(group_of[u]), int(group_of[v])) for (u, v) in cross}
    assert pairs == {(i, j) for i in range(b) for j in range(b) if i != j}


# ---------------------------------------------------------------------------
# construction / facade / fail-fast validation
# ---------------------------------------------------------------------------


def test_facade_dispatches_on_levels():
    rg, assign = region_tree(2, 2, 3, seed=0)
    cp = ControlPlane(rg, levels=2, region_of=assign, **PYM)
    assert isinstance(cp, RegionalControlPlane)
    assert cp.levels == 2 and cp.R == 2 and cp.leaf_regions == 4
    assert all(isinstance(c, RegionalControlPlane) for c in cp.regions)
    # the solver config must never see the nesting kwargs
    cp.register_tenant("a")
    cp.submit("a", DataflowPath.make([0.0, 0.1], [1.0], 0, 1))
    cp.pump()
    cp.check_invariants()
    # levels=1 on the facade IS the flat plane (same object kind)
    flat = ControlPlane(rg, levels=1, region_of=assign, **PYM)
    assert isinstance(flat, RegionalControlPlane) and flat.R == 4
    # regions= alone resolves branching when it is a perfect power
    cp3 = ControlPlane(rg, levels=2, regions=4, **PYM)
    assert isinstance(cp3, RegionalControlPlane) and cp3.R == 2
    # deeper nesting recurses
    rg3, assign3 = region_tree(3, 2, 3, seed=1)
    cp4 = ControlPlane(rg3, levels=3, region_of=assign3, **PYM)
    assert isinstance(cp4, RegionalControlPlane)
    assert all(
        isinstance(c, RegionalControlPlane) for c in cp4.regions)
    assert all(c.levels == 2 for c in cp4.regions)


def test_nesting_kwargs_fail_fast():
    """Contradictory regions= / levels= / branching= / region_of=
    combinations raise with a clear message instead of silently building
    some other plane (mirrors the flat plane's region_of contradiction
    check)."""
    rg, assign = region_tree(2, 2, 3, seed=0)  # 4 leaves, n=12
    with pytest.raises(ValueError, match="levels=0"):
        ControlPlane(rg, levels=0)
    with pytest.raises(ValueError, match="not a perfect levels=2 power"):
        ControlPlane(rg, levels=2, regions=7, **PYM)
    with pytest.raises(ValueError, match="contradicts levels=2 x branching=3"):
        ControlPlane(rg, levels=2, branching=3, regions=4, **PYM)
    with pytest.raises(ValueError, match="requires a hierarchical plane"):
        ControlPlane(rg, branching=3, **PYM)
    with pytest.raises(ValueError, match="contradicts region_of"):
        ControlPlane(rg, levels=2, region_of=assign, regions=9, **PYM)
    with pytest.raises(ValueError, match="contradicts levels=2 x branching=3"):
        ControlPlane(rg, levels=2, branching=3, region_of=assign, **PYM)
    with pytest.raises(ValueError, match="branching=5 contradicts 3 leaf"):
        resolve_nesting(1, 5, 3)
    # direct construction of the broker resolves the nesting kwargs the
    # same way: one level takes no branching=, and two levels need a
    # perfect-power leaf count
    with pytest.raises(ValueError, match="not a perfect levels=2 power"):
        RegionalControlPlane(rg, regions=2, levels=2, **PYM)
    with pytest.raises(ValueError, match="hierarchical"):
        RegionalControlPlane(rg, regions=2, branching=2, **PYM)


# ---------------------------------------------------------------------------
# nested-plane fuzz (conservation at every level)
# ---------------------------------------------------------------------------


def _fuzz_hierarchy(cp, rg, seed, steps=60):
    """Adversarial interleaving of every public operation; every step
    checks each level's ledger, cut conservation, spanning-handle
    integrity, and the cross-level write-through reassembly."""
    rng = np.random.default_rng(seed)
    failed_nodes: list[int] = []
    failed_cuts: list[tuple[int, int]] = []
    cuts = sorted(cp.cut_base)
    for step in range(steps):
        op = rng.choice(
            ["submit", "pump", "release", "fail_node", "restore_node",
             "partition", "heal", "defrag"],
            p=[0.30, 0.25, 0.13, 0.08, 0.08, 0.05, 0.05, 0.06],
        )
        if op == "submit":
            df = random_dataflow(rg, 4, seed=1000 * seed + step,
                                 creq_range=(0.05, 0.3),
                                 breq_range=(0.5, 3.0))
            cp.submit(str(rng.choice(["a", "b", "c"])), df,
                      klass=int(rng.integers(0, 3)))
        elif op == "pump":
            cp.pump(rounds=int(rng.integers(1, 3)))
        elif op == "release":
            ids = cp.active_ids()
            if ids:
                cp.release(int(rng.choice(ids)))
        elif op == "fail_node" and len(failed_nodes) < 3:
            v = int(rng.integers(0, rg.n))
            if v not in failed_nodes:
                cp.fail_node(v)
                failed_nodes.append(v)
        elif op == "restore_node" and failed_nodes:
            cp.restore_node(failed_nodes.pop(
                int(rng.integers(0, len(failed_nodes)))))
        elif op == "partition" and cuts and len(failed_cuts) < 2:
            e = cuts[int(rng.integers(0, len(cuts)))]
            if e not in failed_cuts:
                cp.fail_link(*e)
                failed_cuts.append(e)
        elif op == "heal" and failed_cuts:
            cp.restore_link(*failed_cuts.pop(
                int(rng.integers(0, len(failed_cuts)))))
        elif op == "defrag":
            for res in cp.defrag():
                assert res.objective_after >= res.objective_before
        cp.check_invariants()
    cp.flush()
    cp.check_invariants()
    led = cp.conservation()
    assert led["ok"] and led["in_flight"] == 0
    return led


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_hierarchy_conservation(seed):
    rg, assign = region_tree(2, 3, 4, seed=3)  # 9 leaves, n=36
    cp = RegionalControlPlane(
        rg, levels=2, region_of=assign, micro_batch=6, max_attempts=3,
        seed=seed, policy=FairSharePolicy(slack=0.4), **PYM,
    )
    cp.register_tenant("a", weight=3.0)
    cp.register_tenant("b", weight=1.0)
    cp.register_tenant("c", weight=2.0, budget=1.5)
    led = _fuzz_hierarchy(cp, rg, seed)
    assert led["submitted"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("seed", [2, 3])
def test_fuzz_hierarchy_conservation_3level(seed):
    rg, assign = region_tree(3, 2, 3, seed=4)  # 8 leaves, n=24
    cp = RegionalControlPlane(
        rg, levels=3, region_of=assign, micro_batch=6, max_attempts=3,
        seed=seed, policy=FairSharePolicy(slack=0.4), **PYM,
    )
    for t, w in (("a", 3.0), ("b", 1.0), ("c", 2.0)):
        cp.register_tenant(t, weight=w)
    led = _fuzz_hierarchy(cp, rg, seed, steps=100)
    assert led["submitted"] > 0


# ---------------------------------------------------------------------------
# recursive spanning decomposition
# ---------------------------------------------------------------------------


def _tree_plane(levels=2, b=2, k=4, seed=0, **kw):
    rg, assign = region_tree(levels, b, k, seed=seed)
    cp = RegionalControlPlane(
        rg, levels=levels, region_of=assign, micro_batch=8,
        max_attempts=4, seed=seed, **PYM, **kw,
    )
    cp.register_tenant("a")
    return rg, assign, cp


def _cross_tree_df(rg, creq=0.1, breq=0.5):
    """A dataflow pinned from the first to the last node — guaranteed to
    cross the top-level cut of any depth-first region tree."""
    return DataflowPath.make(
        [0.0, creq, creq, 0.0], [breq, breq, breq], 0, rg.n - 1)


def test_cross_group_spanning_splits_at_every_level():
    """A dataflow crossing the top-level cut is split there, and each
    segment is admitted by the child plane — which may split again at its
    own cuts: the parts of the top span are broker-held spans inside the
    children, recursively well-formed at every level."""
    rg, assign, cp = _tree_plane(levels=2, b=2, k=4)
    rid = cp.submit("a", _cross_tree_df(rg))
    (st,) = cp.pump()
    assert st.rid == rid and len(st.parts) == 2 and len(st.cuts) == 1
    assert cp.span_stats["admitted"] == 1
    # each part is a live broker-held reservation inside its child
    for part in st.parts:
        child = cp.regions[part.region]
        assert part.tid in child._broker_held
        assert part.tid in child._span_active
    cp.check_invariants()
    # src and dst leaves are in different groups AND different leaf
    # regions inside them, so at least one child had to split again
    # (its broker-held span has its own cut) or place via its gateway
    assert cp.region_of[0] != cp.region_of[rg.n - 1]
    cp.release(rid)
    cp.check_invariants()
    led = cp.conservation()
    assert led["active"] == 0 and led["ok"]
    # the teardown released every child holding too
    for child in cp.regions:
        assert not child._broker_held


def test_gateway_failure_displaces_top_span_and_heals():
    rg, assign, cp = _tree_plane(levels=2, b=2, k=4)
    rid = cp.submit("a", _cross_tree_df(rg))
    (st,) = cp.pump()
    (u, v) = st.cuts[0]
    alive, requeued = cp.fail_node(u)
    assert st in requeued
    assert rid not in cp._span_active
    for child in cp.regions:
        assert not child._broker_held  # sibling reservations torn down
    cp.check_invariants()
    assert cp.conservation()["ok"]
    cp.restore_node(u)
    got = cp.pump(rounds=4)
    assert any(getattr(t, "rid", None) == rid for t in got)
    cp.check_invariants()


def test_cut_link_failure_displaces_and_requeues():
    rg, assign, cp = _tree_plane(levels=2, b=2, k=4)
    rid = cp.submit("a", _cross_tree_df(rg))
    (st,) = cp.pump()
    alive, requeued = cp.fail_link(*st.cuts[0])
    assert st in requeued and rid not in cp._span_active
    cp.check_invariants()
    # full bandwidth back on the ledger for the failed (but intact) link
    cp.restore_link(*st.cuts[0])
    assert cp.cut_residual[st.cuts[0]] == cp.cut_base[st.cuts[0]]
    got = cp.pump(rounds=4)
    assert any(getattr(t, "rid", None) == rid for t in got)
    cp.check_invariants()


def test_child_displacement_chains_up_through_broker_hook():
    """Churn INSIDE a child that kills a top-level segment must tear the
    whole composite down at the top (on_broker_displace), not leak the
    sibling reservations."""
    rg, assign, cp = _tree_plane(levels=2, b=2, k=4)
    rid = cp.submit("a", _cross_tree_df(rg))
    (st,) = cp.pump()
    # fail a node the span actually uses strictly inside one child (not a
    # top-level gateway of this span's cut)
    gateways = {v for e in st.cuts for v in e}
    used = [
        v for v in range(rg.n)
        if v not in gateways and cp._span_uses_node(st, v)
    ]
    assert used, "span places no interior node; pick a bigger instance"
    cp.fail_node(used[0])
    assert rid not in cp._span_active
    for child in cp.regions:
        assert not child._broker_held
    cp.check_invariants()
    assert cp.conservation()["ok"]


def test_leaf_preemption_of_a_segment_chains_up_to_the_top():
    """A top-class request inside a leaf that preempts a segment of a
    top-level span: the leaf hands the victim to its broker, which hands
    the displaced reservation up, and the top level tears the whole
    composite down and requeues it — nothing leaks at any level."""
    rg, assign, cp = _tree_plane(levels=2, b=2, k=4)
    span_rid = cp.submit("a", _cross_tree_df(rg, creq=0.5))
    (st,) = cp.pump()
    u = next(
        view.to_global(lv)
        for view, leaf in cp.leaf_planes()
        for tk in leaf.placer.tickets.values()
        for lv, c in sorted(tk.node_load.items()) if c > 0
    )
    w = next(x for x in range(rg.n) if x != u and assign[x] == assign[u])
    hog = cp.submit("a", DataflowPath.make([float(rg.cap[u]), 0.0], [0.1],
                                           u, w), klass=2)
    cp.pump()
    assert hog in cp.active_ids()
    assert cp.span_stats["displaced"] == 1
    assert cp._span_active.get(span_rid) is not st
    cp.check_invariants()
    assert cp.conservation()["ok"]


def test_release_rejects_parent_held_rid_at_child_level():
    rg, assign, cp = _tree_plane(levels=2, b=2, k=4)
    cp.submit("a", _cross_tree_df(rg))
    (st,) = cp.pump()
    part = st.parts[0]
    with pytest.raises(KeyError, match="broker"):
        cp.regions[part.region].release(part.tid)


def test_flat_broker_admit_release_roundtrip():
    """The flat plane's parent-broker interface on its own: in-region and
    cross-region broker reservations are first-class ledger entries,
    invisible to active_ids, idempotently releasable, and protected from
    plain release()."""
    rg, assign = region_tree(1, 3, 4, seed=5)  # flat: 3 meshed regions
    cp = RegionalControlPlane(rg, region_of=assign, seed=0, **PYM)
    cp.register_tenant("a")
    # in-region reservation
    r1 = cp.broker_admit("a", DataflowPath.make([0.0, 0.1], [0.5], 0, 1))
    # cross-region reservation (spans the plane's own cut)
    r2 = cp.broker_admit("a", _cross_tree_df(rg))
    assert r1 is not None and r2 is not None
    assert cp.active_ids() == []  # parent-held: not caller-visible
    assert cp.conservation()["ok"] and cp.conservation()["active"] == 2
    assert cp.broker_uses_node(r1, 0)
    with pytest.raises(KeyError, match="broker"):
        cp.release(r1)
    cp.check_invariants()
    cp.broker_release(r1)
    cp.broker_release(r1)  # idempotent
    cp.broker_release(r2)
    led = cp.conservation()
    assert led["active"] == 0 and led["released"] == 2 and led["ok"]
    cp.check_invariants()


# ---------------------------------------------------------------------------
# tree-structured gossip / resident state
# ---------------------------------------------------------------------------


def test_tree_gossip_message_and_record_budget():
    """Each level's bus carries only that level's siblings: messages per
    round are O(branching * fanout) per component, and every message holds
    at most ``branching`` aggregated records — never one record per leaf
    region, let alone per node."""
    rg, assign = region_tree(2, 4, 3, seed=6)  # 16 leaves, n=48
    cp = RegionalControlPlane(
        rg, levels=2, region_of=assign, fanout=2, seed=0, **PYM)
    cp.register_tenant("a")
    rng = np.random.default_rng(0)
    for i in range(24):
        cp.submit("a", random_dataflow(rg, 3, seed=i,
                                       creq_range=(0.05, 0.2),
                                       breq_range=(0.5, 2.0)))
    cp.pump(rounds=6)
    top = cp.bus.gossip_stats()
    assert top["messages_per_round"] <= cp.R * cp.bus.fanout
    assert top["records_per_message"] <= cp.R
    for child in cp.regions:
        st = child.bus.gossip_stats()
        assert st["messages_per_round"] <= child.R * child.bus.fanout
        assert st["records_per_message"] <= child.R
    # flat plane over the same 16 leaves: every record still bounded by R,
    # but R is the GLOBAL region count — 4x the hierarchy's branching
    flat = RegionalControlPlane(rg, region_of=assign, fanout=2, seed=0,
                                **PYM)
    flat.register_tenant("a")
    for i in range(24):
        flat.submit("a", random_dataflow(rg, 3, seed=i,
                                         creq_range=(0.05, 0.2),
                                         breq_range=(0.5, 2.0)))
    flat.pump(rounds=6)
    fst = flat.bus.gossip_stats()
    assert fst["records_per_message"] > cp.R  # the flat view is R-sized
    cp.check_invariants()


def test_gossip_payload_accounting():
    """records_sent / payload_sent count what the wire would carry:
    records x (3 scalars + committed + queued entries)."""
    from repro.service import GossipBus

    bus = GossipBus(3, fanout=2, seed=0)
    bus.publish(0, {"a": 1.0, "b": 2.0}, {"a": 0.5}, 7.0)
    bus.publish(1, {"a": 0.0}, {}, 3.0)
    sent = bus.tick()
    assert sent == 6  # R * fanout
    # regions 0 and 1 each pushed their 1-record view to 2 peers; region 2
    # pushed an empty view to 2 peers
    assert bus.records_sent == 4
    # region 0's record: 3 + 2 committed + 1 queued = 6; region 1's:
    # 3 + 1 + 0 = 4; each carried twice
    assert bus.payload_sent == 2 * 6 + 2 * 4
    st = bus.gossip_stats()
    assert st["payload_per_round"] == bus.payload_sent
    assert st["records_per_message"] == pytest.approx(4 / 6)


def test_resident_state_hierarchy_strictly_below_flat():
    """The headline scaling claim at test size: over the same 16-leaf
    tree, the 2-level plane's largest component (solve size + peer/id
    tables) is strictly smaller than the flat plane's — the flat broker
    must hold every gateway id and every region as a peer."""
    rg, assign = region_tree(2, 4, 3, seed=7)  # 16 leaves, n=48
    flat = RegionalControlPlane(rg, region_of=assign, seed=0, **PYM)
    hier = RegionalControlPlane(
        rg, levels=2, region_of=assign, seed=0, **PYM)
    f = flat.resident_state_report()
    h = hier.resident_state_report()
    assert h["max_component_state"] < f["max_component_state"]
    # and no hierarchy component holds an id table sized like the flat
    # broker's global boundary
    flat_broker = next(
        c for c in f["components"] if c["component"] == "broker")
    for c in h["components"]:
        assert c.get("id_table", 0) < flat_broker["id_table"]


def test_coordination_report_nests():
    rg, assign, cp = _tree_plane(levels=2, b=2, k=3)
    cp.submit("a", _cross_tree_df(rg))
    cp.pump(rounds=2)
    rep = cp.coordination_report()
    assert rep["levels"] == 2 and rep["branching"] == 2
    assert rep["leaf_regions"] == 4
    assert len(rep["children"]) == 2
    assert rep["resident"]["max_component_state"] > 0
    assert rep["gossip"]["n_regions"] == 2
    fair = cp.fairness_report()
    assert "coordination" in fair


# ---------------------------------------------------------------------------
# congestion-aware k-chain routing through the tree
# ---------------------------------------------------------------------------


def test_routing_knobs_propagate_down_the_tree():
    """chain_k / congestion_weight / max_cum_attempts reach every nested
    plane (and never leak into the solver config)."""
    rg, assign = region_tree(2, 2, 3, seed=0)
    cp = ControlPlane(rg, levels=2, region_of=assign, chain_k=3,
                      congestion_weight=0.5, max_cum_attempts=7, **PYM)
    planes = [cp]
    while planes:
        p = planes.pop()
        assert p.chain_k == 3
        assert p.congestion_weight == 0.5
        assert p.max_cum_attempts == 7
        if p.levels > 1:
            planes.extend(p.regions)
    cp.register_tenant("a")
    cp.submit("a", _cross_tree_df(rg))
    cp.pump()
    cp.check_invariants()


def test_top_level_k_chains_exist_on_sibling_mesh():
    """Top-level siblings are all-to-all, so Yen finds a 2-hop bypass
    behind every direct chain — the racer has real alternatives at the
    top of the tree too."""
    rg, assign, cp = _tree_plane(levels=2, b=3, k=3, chain_k=2)
    chains = cp._region_chains(0, 1, {})
    assert chains[0] == [0, 1] and len(chains) == 2
    assert chains[1] == [0, 2, 1]


def test_congestion_published_at_every_level():
    """Each level's bus carries occupancy estimates for its own gateway
    nodes (folded recursively out of the children via node_occupancy)."""
    rg, assign, cp = _tree_plane(levels=2, b=2, k=4, fanout=1)
    cp.submit("a", _cross_tree_df(rg))
    cp.pump()
    view = cp.bus.congestion_view(0)
    own = cp._gateways_of.get(0, ())
    assert own and all(u in view for u in own)
    assert all(0.0 <= view[u] <= 1.0 for u in view)
    for g in range(cp.R):
        for u in cp._gateways_of.get(g, ()):
            assert 0.0 <= cp.node_occupancy(int(u)) <= 1.0
    # the child planes publish their own (local-id) gateway estimates too
    child = cp.regions[0]
    crec = child.bus.views[0].get(0)
    assert crec is not None and isinstance(crec.congestion, dict)


def test_hierarchy_cut_fail_restore_keeps_ledger_coherent():
    """Top-level cut fail/restore under a standing cross-group span: the
    healed cut reappears with its full residual, double fail/restore is
    idempotent, and the displaced request is readmitted."""
    rg, assign, cp = _tree_plane(levels=2, b=2, k=4)
    rid = cp.submit("a", _cross_tree_df(rg))
    (st,) = cp.pump()
    e = st.cuts[0]
    cp.fail_link(*e)
    cp.fail_link(*e)  # idempotent
    cp.check_invariants()
    assert cp.cut_residual[e] == pytest.approx(cp.cut_base[e])
    assert all(-1e-6 <= cp.cut_residual[c] <= cp.cut_base[c] + 1e-6
               for c in cp.cut_base)
    cp.restore_link(*e)
    cp.restore_link(*e)  # idempotent
    assert cp.cut_residual[e] == pytest.approx(cp.cut_base[e])
    got = cp.pump(rounds=4)
    assert any(getattr(t, "rid", None) == rid for t in got)
    cp.check_invariants()


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_hierarchy_k_chain_conservation(seed):
    """The full fuzz suite with the k-chain racer live at every level of
    a 3-sibling tree (real bypass chains exist top-level)."""
    rg, assign = region_tree(2, 3, 4, seed=3)
    cp = RegionalControlPlane(
        rg, levels=2, region_of=assign, micro_batch=6, max_attempts=3,
        seed=seed, chain_k=3, policy=FairSharePolicy(slack=0.4), **PYM,
    )
    cp.register_tenant("a", weight=3.0)
    cp.register_tenant("b", weight=1.0)
    cp.register_tenant("c", weight=2.0, budget=1.5)
    led = _fuzz_hierarchy(cp, rg, seed)
    assert led["submitted"] > 0
