"""Decentralized regional control plane: partitioning, gossiped share
estimates, cut-edge two-phase commit, and the property suite — seeded fuzz
over adversarial interleavings of submit/pump/gossip/partition/heal/
release/fail/defrag across R regions, asserting the global conservation
ledger, bit-for-bit R=1 identity with the centralized plane, and
no-over-commit under maximally stale gossip."""
import collections

import numpy as np
import pytest

from repro.core import DataflowPath, random_dataflow, waxman
from repro.service import (
    ControlPlane,
    FairSharePolicy,
    GossipBus,
    RegionalControlPlane,
    cut_edges,
    partition_regions,
    region_subgraph,
    split_dataflow,
)

PYM = dict(method="leastcost_python")  # pure-python backend: fast, no jit


# ---------------------------------------------------------------------------
# partitioning / subgraphs
# ---------------------------------------------------------------------------


def test_partition_is_balanced_deterministic_and_total():
    rg = waxman(23, seed=3)
    a1 = partition_regions(rg, 4, seed=5)
    a2 = partition_regions(rg, 4, seed=5)
    np.testing.assert_array_equal(a1, a2)
    counts = collections.Counter(a1.tolist())
    assert set(counts) == {0, 1, 2, 3}
    assert max(counts.values()) - min(counts.values()) <= 1
    # every node assigned exactly once, R clamped to n
    assert partition_regions(rg, 100, seed=0).max() == rg.n - 1


def test_region_subgraph_masks_foreign_capacity_and_links():
    rg = waxman(16, seed=2)
    assign = partition_regions(rg, 2, seed=0)
    sub = region_subgraph(rg, assign, 0)
    mine = assign == 0
    assert np.all(sub.cap[~mine] == 0)
    np.testing.assert_array_equal(sub.cap[mine], rg.cap[mine])
    # no link leaves the region
    for (u, v) in sub.edges():
        assert mine[u] and mine[v]
    # the masked links are exactly the complement of cuts + foreign links
    cuts = set(cut_edges(rg, assign))
    for (u, v) in rg.edges():
        if mine[u] and mine[v]:
            assert np.isfinite(sub.lat[u, v])
        else:
            assert not np.isfinite(sub.lat[u, v])
            if mine[u] != mine[v]:
                assert (u, v) in cuts


def test_r1_subgraph_is_the_whole_graph_bitwise():
    rg = waxman(12, seed=7)
    assign = partition_regions(rg, 1)
    sub = region_subgraph(rg, assign, 0)
    np.testing.assert_array_equal(sub.cap, rg.cap)
    np.testing.assert_array_equal(sub.bw, rg.bw)
    np.testing.assert_array_equal(sub.lat, rg.lat)
    assert cut_edges(rg, assign) == []


def test_split_dataflow_conserves_requirements():
    df = DataflowPath.make([0.1, 0.2, 0.3, 0.4], [1.0, 2.0, 3.0], src=0, dst=9)
    a, b = split_dataflow(df, 1, 4, 5)
    # ghost gateway endpoints: zero-compute nodes pinned at the cut's
    # tail (a.dst) / head (b.src) gateways, carrying the cut edge's
    # bandwidth from the real boundary node to the gateway
    assert a.src == 0 and a.dst == 4 and b.src == 5 and b.dst == 9
    np.testing.assert_array_equal(
        a.creq, np.concatenate([df.creq[:2], [np.float32(0)]]))
    np.testing.assert_array_equal(
        b.creq, np.concatenate([[np.float32(0)], df.creq[2:]]))
    # real compute is conserved across the split
    assert float(np.sum(a.creq) + np.sum(b.creq)) == pytest.approx(
        float(np.sum(df.creq)))
    # the segments keep their interior edges and each carries the cut
    # edge's requirement (breq[1]) on its gateway-transport edge
    np.testing.assert_array_equal(a.breq, [1.0, 2.0])
    np.testing.assert_array_equal(b.breq, [2.0, 3.0])


def test_split_dataflow_chain_transit_segments():
    """Equal consecutive splits make pure transit segments: no real
    dataflow nodes, only ghost gateway endpoints carrying the one cut
    dataflow edge across the region."""
    from repro.service import split_dataflow_chain

    df = DataflowPath.make([0.5, 0.75], [2.0], src=0, dst=9)
    a, t, b = split_dataflow_chain(df, [0, 0], [(1, 4), (5, 8)])
    np.testing.assert_array_equal(a.creq, [0.5, 0.0])
    np.testing.assert_array_equal(a.breq, [2.0])
    assert (a.src, a.dst) == (0, 1)
    # the transit segment spans the middle region gateway-to-gateway
    np.testing.assert_array_equal(t.creq, [0.0, 0.0])
    np.testing.assert_array_equal(t.breq, [2.0])
    assert (t.src, t.dst) == (4, 5)
    np.testing.assert_array_equal(b.creq, [0.0, 0.75])
    assert (b.src, b.dst) == (8, 9)
    # a transit region whose in/out gateway coincide needs no edge at all
    (_, t1, _) = split_dataflow_chain(df, [0, 0], [(1, 4), (4, 8)])
    np.testing.assert_array_equal(t1.creq, [0.0])
    assert t1.breq.size == 0 and (t1.src, t1.dst) == (4, 4)


# ---------------------------------------------------------------------------
# gossip fabric
# ---------------------------------------------------------------------------


def test_gossip_round_costs_exactly_R_times_fanout():
    bus = GossipBus(4, fanout=2, seed=0)
    for r in range(4):
        bus.publish(r, {"a": float(r)}, {}, 1.0)
    for _ in range(5):
        assert bus.tick() == 4 * 2
    assert bus.messages_sent == 5 * 4 * 2


def test_gossip_merge_keeps_freshest_version_only():
    bus = GossipBus(3, fanout=2, seed=1)
    bus.publish(0, {"a": 1.0}, {}, 1.0)
    bus.tick()
    bus.publish(0, {"a": 5.0}, {}, 1.0)  # version 2 supersedes
    for _ in range(3):
        bus.tick()
    for r in range(3):
        rec = bus.views[r].get(0)
        assert rec is not None and rec.version == 2
        assert rec.committed["a"] == 5.0
    assert bus.max_staleness() == 0


def test_full_fanout_disseminates_in_one_round():
    bus = GossipBus(5, fanout=4, seed=3)  # push to everyone
    for r in range(5):
        bus.publish(r, {"t": float(r)}, {}, 0.0)
    bus.tick()
    assert bus.max_staleness() == 0
    for r in range(5):
        assert bus.remote_committed(r)["t"] == sum(
            float(o) for o in range(5) if o != r
        )


def test_zero_fanout_never_disseminates():
    bus = GossipBus(4, fanout=0, seed=0)
    for r in range(4):
        bus.publish(r, {"t": 1.0}, {}, 0.0)
    for _ in range(10):
        assert bus.tick() == 0
    assert bus.messages_sent == 0
    assert all(bus.remote_committed(r) == {} for r in range(4))


# ---------------------------------------------------------------------------
# facade + spanning placements
# ---------------------------------------------------------------------------


def test_controlplane_facade_dispatches_on_regions():
    rg = waxman(12, seed=1)
    assert isinstance(ControlPlane(rg, **PYM), ControlPlane)
    assert isinstance(ControlPlane(rg, regions=1, **PYM), ControlPlane)
    cp = ControlPlane(rg, regions=3, seed=0, **PYM)
    assert isinstance(cp, RegionalControlPlane)
    assert cp.R == 3


def _regional(n=18, R=2, seed=0, **kw):
    rg = waxman(n, seed=seed)
    cp = RegionalControlPlane(rg, regions=R, seed=seed, **PYM, **kw)
    cp.register_tenant("a", weight=3.0)
    cp.register_tenant("b", weight=1.0)
    return rg, cp


def _spanning_df(cp, creq=0.3, breq=1.0):
    """A p=2 request pinned to the two endpoints of the best cut edge —
    placeable only by decomposition across the cut."""
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    return DataflowPath.make([creq, creq], [breq], src=u, dst=v)


def test_spanning_request_places_by_two_phase_commit():
    rg, cp = _regional()
    df = _spanning_df(cp)
    rid = cp.submit("a", df)
    (t,) = cp.pump()
    cp.check_invariants()
    assert t.rid == rid
    (u, v) = t.cut
    assert cp.region_of[u] != cp.region_of[v]
    # one segment reserved in each region, under the right tenant
    part_a, part_b = t.parts
    assert [part_a.region, part_b.region] == [
        int(cp.region_of[u]), int(cp.region_of[v])]
    assert cp.regions[part_a.region].placer.tickets[part_a.tid].tenant == "a"
    # parts record the bijection generation they were minted under
    assert part_a.version == cp.views[part_a.region].version
    # the reserved segments live in the regions' LOCAL id spaces: the
    # gateway pins translate back to the global cut endpoints
    assert cp.views[part_a.region].to_global(part_a.seg.dst) == u
    assert cp.views[part_b.region].to_global(part_b.seg.src) == v
    # the cut reservation left the broker ledger
    assert cp.cut_residual[t.cut] == pytest.approx(cp.cut_base[t.cut] - 1.0)
    assert cp.engine_stats().twopc_messages >= 4  # 2 prepares + 2 commits
    led = cp.conservation()
    assert led["ok"] and led["active"] == 1
    # release returns every reservation
    cp.release(rid)
    cp.check_invariants()
    assert cp.cut_residual[t.cut] == pytest.approx(cp.cut_base[t.cut])
    assert all(not c.placer.tickets for c in cp.regions)
    assert cp.conservation()["released"] == 1


def test_spanning_infeasible_rolls_back_and_eventually_drops():
    rg, cp = _regional(max_attempts=3)
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    huge = float(np.sum(rg.cap)) + 1.0  # fits nowhere, ever
    df = DataflowPath.make([0.0, huge, 0.0], [1.0, 1.0], src=u, dst=v)
    cp.submit("a", df)
    for _ in range(3):
        cp.pump()
        cp.check_invariants()
        # nothing was partially committed by the failed 2PC attempts
        assert all(not c.placer.tickets for c in cp.regions)
        assert all(
            cp.cut_residual[e] == pytest.approx(cp.cut_base[e])
            for e in cp.cut_base
        )
    led = cp.conservation()
    assert led["ok"] and led["dropped"] == 1 and led["active"] == 0


def test_dropped_local_requests_do_not_leak_rid_maps():
    """A region dropping a local request must clear the broker's
    global-rid bookkeeping for it (the maps are otherwise append-only)."""
    rg, cp = _regional(max_attempts=1)
    nodes = np.nonzero(cp.region_of == 0)[0]
    huge = float(np.sum(rg.cap)) + 1.0
    df = DataflowPath.make([0.0, huge, 0.0], [1.0, 1.0],
                           src=int(nodes[0]), dst=int(nodes[1]))
    rid = cp.submit("a", df)  # in-region, infeasible forever
    assert rid in cp._local
    cp.pump()
    cp.check_invariants()
    assert cp.conservation()["dropped"] == 1
    assert rid not in cp._local
    assert not cp._grid_of


def test_spanning_survives_regional_defrag():
    rg, cp = _regional()
    rid = cp.submit("a", _spanning_df(cp))
    cp.pump()
    results = cp.defrag()
    cp.check_invariants()  # handle integrity re-checked (tids preserved)
    assert len(results) == cp.R
    cp.release(rid)  # the handle still resolves after re-optimization
    cp.check_invariants()
    assert cp.conservation()["released"] == 1


def test_cut_link_partition_displaces_and_heals():
    rg, cp = _regional()
    rid = cp.submit("a", _spanning_df(cp))
    (t,) = cp.pump()
    alive, requeued = cp.fail_link(*t.cut)  # partition the region pair
    cp.check_invariants()
    assert alive == [] and len(requeued) == 2  # both segments torn down
    led = cp.conservation()
    assert led["active"] == 0 and led["queued"] == 1  # requeued, not dropped
    assert rid not in cp.active_ids()
    cp.restore_link(*t.cut)  # heal
    out = cp.pump()
    cp.check_invariants()
    assert [s.rid for s in out] == [rid]  # same rid readmitted
    assert cp.conservation()["active"] == 1


def test_gateway_node_failure_displaces_spanning_ticket():
    rg, cp = _regional()
    rid = cp.submit("a", _spanning_df(cp))
    (t,) = cp.pump()
    gateway = t.cut[0]
    cp.fail_node(gateway)
    cp.check_invariants()
    assert rid not in cp.active_ids()
    led = cp.conservation()
    assert led["ok"] and led["dropped"] == 0  # displaced to a queue
    cp.restore_node(gateway)
    cp.check_invariants()


def test_spanning_fairness_uses_gossiped_estimates():
    """With instant gossip, a tenant far over its estimated global share is
    not selected for spanning drains before the under-served one."""
    rg, cp = _regional(R=2, fanout=1)
    # saturate tenant b's global holdings via direct in-region admissions
    for r in range(cp.R):
        for tk in range(2):
            nodes = np.nonzero(cp.region_of == r)[0]
            df = DataflowPath.make([0.4], [], src=int(nodes[0]),
                                  dst=int(nodes[0]))
            cp.regions[r].placer.admit(df, tenant="b")
    cp.submit("b", _spanning_df(cp, creq=0.2))
    cp.submit("a", _spanning_df(cp, creq=0.2))
    out = cp.pump()  # gossip spreads b's holdings before the span drain
    # a (weight 3, holding 0 globally) is the most under-served tenant, so
    # the broker drains it first even though b submitted first
    assert out and out[0].tenant == "a"
    cp.check_invariants()


def test_region_drain_counts_segments_it_holds_for_the_broker():
    """A region's fair-share drain sees the spanning segments it holds for
    the broker (placer tickets, not its own requests): with gossip off,
    the tenant holding a segment there drains after one holding nothing,
    though both are equally weighted and it sorts first by name."""
    rg = waxman(18, seed=0)
    cp = RegionalControlPlane(rg, regions=2, seed=0, fanout=0, micro_batch=1,
                              **PYM)
    cp.register_tenant("a")
    cp.register_tenant("b")
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    rid = cp.submit("a", DataflowPath.make([0.5, 0.5], [1.0], src=u, dst=v))
    assert [getattr(t, "rid", None) for t in cp.pump()] == [rid]
    x = int(np.nonzero(cp.region_of == cp.region_of[u])[0][0])
    for tenant in ("a", "b"):
        cp.submit(tenant, DataflowPath.make([0.1], [], src=x, dst=x))
    (first,) = cp.pump()
    assert first.tenant == "b"
    cp.check_invariants()


# ---------------------------------------------------------------------------
# property suite: seeded fuzz across R regions
# ---------------------------------------------------------------------------


def _fuzz_plane(cp, rg, seed, steps=60, df_gen=None):
    """Adversarial interleaving of every public operation; every step
    checks placer conservation, the global ledger, cut-bandwidth
    conservation, and spanning-handle integrity.  ``df_gen(rng, step)``
    overrides the submitted workload (e.g. the multi-hop matrix biases it
    toward far-spanning endpoint pairs)."""
    rng = np.random.default_rng(seed)
    failed_nodes: list[int] = []
    failed_cuts: list[tuple[int, int]] = []
    cuts = sorted(cp.cut_base) if hasattr(cp, "cut_base") else []
    for step in range(steps):
        op = rng.choice(
            ["submit", "pump", "release", "fail_node", "restore_node",
             "partition", "heal", "defrag"],
            p=[0.30, 0.25, 0.13, 0.08, 0.08, 0.05, 0.05, 0.06],
        )
        if op == "submit":
            if df_gen is not None:
                df = df_gen(rng, step)
            else:
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
    # mid-stream the ledger already accounts for in-flight batches; drain
    # the pipeline so the end-state equality below is exact
    cp.flush()
    cp.check_invariants()
    led = cp.conservation()
    assert led["ok"] and led["in_flight"] == 0
    assert led["submitted"] == (
        led["queued"] + led["active"] + led["released"] + led["dropped"]
    )
    return led


def _fresh_regional(R, seed, fanout=2, gossip_period=1, **kw):
    rg = waxman(14, seed=4)
    cp = RegionalControlPlane(
        rg, regions=R, micro_batch=6, max_attempts=3, seed=seed,
        fanout=fanout, gossip_period=gossip_period,
        policy=FairSharePolicy(slack=0.4), **PYM, **kw,
    )
    cp.register_tenant("a", weight=3.0)
    cp.register_tenant("b", weight=1.0)
    cp.register_tenant("c", weight=2.0, budget=1.5)
    return rg, cp


@pytest.mark.parametrize("R", [1, 2, 4])
@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_regional_conservation(R, seed):
    rg, cp = _fresh_regional(R, seed)
    led = _fuzz_plane(cp, rg, seed)
    assert led["submitted"] > 0


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_regional_conservation_pipelined(seed):
    """Depth-2 admission windows in every region: optimistic local batches
    outstanding across pumps, spanning 2PC interleaved, same invariants."""
    rg, cp = _fresh_regional(2, seed, pipeline_depth=2)
    led = _fuzz_plane(cp, rg, seed)
    assert led["submitted"] > 0


def test_spanning_2pc_tolerates_in_flight_batch():
    """The broker's 2PC reserves host-side (bumping the residual *version*,
    not the staleness *epoch*), so a spanning admission while a local batch
    is optimistically in flight just makes that batch's commit re-validate —
    nothing deadlocks, nothing overcommits."""
    rg, cp = _regional(pipeline_depth=2)
    intra = [(u, v) for (u, v) in rg.edges()
             if cp.region_of[u] == cp.region_of[v]]
    assert intra, "no intra-region edge; instance too partitioned"
    u, v = intra[0]
    cp.submit("a", DataflowPath.make([0.1, 0.1], [0.5], src=u, dst=v))
    assert cp.pump() == []  # parked in the region's depth-2 window
    assert cp.conservation()["in_flight"] == 1

    rid = cp.submit("a", _spanning_df(cp))
    (t,) = cp.pump()  # 2PC completes around the outstanding batch
    assert t.rid == rid
    assert cp.conservation()["in_flight"] == 1  # local batch still parked
    cp.check_invariants()

    cp.flush()
    led = cp.conservation()
    assert led["ok"] and led["in_flight"] == 0 and led["active"] >= 1
    cp.check_invariants()


@pytest.mark.slow
@pytest.mark.parametrize("R", [2, 4])
@pytest.mark.parametrize("seed", [2, 3, 4, 5])
def test_fuzz_regional_conservation_extended(R, seed):
    """Slow-lane matrix: more seeds, longer interleavings, staler gossip."""
    rg, cp = _fresh_regional(R, seed, fanout=1, gossip_period=3)
    _fuzz_plane(cp, rg, seed, steps=140)


@pytest.mark.parametrize("seed", [0, 1])
def test_r1_regional_bit_identical_to_centralized(seed):
    """The degenerate single-region plane replays the exact centralized
    behavior: same rids, same tickets, same residual arrays bit for bit,
    same ledger — step by step under a fuzzed op sequence."""
    rg = waxman(14, seed=5)
    kw = dict(micro_batch=6, max_attempts=3,
              policy=FairSharePolicy(slack=0.4), **PYM)
    cen = ControlPlane(rg, **kw)
    reg = RegionalControlPlane(rg, regions=1, seed=seed, **kw)
    for cp in (cen, reg):
        cp.register_tenant("a", weight=3.0)
        cp.register_tenant("b", weight=1.0)
    rng = np.random.default_rng(seed)
    failed: list[int] = []
    for step in range(60):
        op = rng.choice(
            ["submit", "pump", "release", "fail", "restore", "defrag"],
            p=[0.35, 0.28, 0.15, 0.08, 0.07, 0.07],
        )
        if op == "submit":
            df = random_dataflow(rg, 4, seed=2000 * seed + step,
                                 creq_range=(0.05, 0.3),
                                 breq_range=(0.5, 3.0))
            t = str(rng.choice(["a", "b"]))
            k = int(rng.integers(0, 3))
            assert cen.submit(t, df, klass=k) == reg.submit(t, df, klass=k)
        elif op == "pump":
            r = int(rng.integers(1, 3))
            assert (
                [t.tid for t in cen.pump(rounds=r)]
                == [t.tid for t in reg.pump(rounds=r)]
            )
        elif op == "release":
            ids = cen.active_ids()
            assert ids == reg.active_ids()
            if ids:
                rid = int(rng.choice(ids))
                cen.release(rid)
                reg.release(rid)
        elif op == "fail" and len(failed) < 3:
            v = int(rng.integers(0, rg.n))
            if v not in failed:
                a1, q1 = cen.fail_node(v)
                a2, q2 = reg.fail_node(v)
                assert [t.tid for t in a1] == [t.tid for t in a2]
                assert [t.tid for t in q1] == [t.tid for t in q2]
                failed.append(v)
        elif op == "restore" and failed:
            v = failed.pop(int(rng.integers(0, len(failed))))
            cen.restore_node(v)
            reg.restore_node(v)
        elif op == "defrag":
            rc = cen.defrag()
            (rr,) = reg.defrag()
            assert (rc.committed, rc.repacked, rc.moved) == (
                rr.committed, rr.repacked, rr.moved)
        # -- bit-for-bit state equality, every step
        inner = reg.regions[0]
        np.testing.assert_array_equal(cen.placer.cap, inner.placer.cap)
        np.testing.assert_array_equal(cen.placer.bw, inner.placer.bw)
        assert sorted(cen.placer.tickets) == sorted(inner.placer.tickets)
        for tid, tk in cen.placer.tickets.items():
            assert tk.mapping == inner.placer.tickets[tid].mapping
        assert cen.conservation() == reg.conservation()
        cen.check_invariants()
        reg.check_invariants()
    # the regional facade spent zero coordination messages at R = 1
    s = reg.engine_stats()
    assert s.gossip_messages == 0 and s.twopc_messages == 0


def test_maximally_stale_gossip_never_overcommits_a_region():
    """fanout=0: estimates never propagate (staleness grows without
    bound), and tenant load is deliberately skewed — yet no admission may
    ever exceed any region's own residual: over-commit safety must come
    from local validation, not from estimate freshness."""
    rg = waxman(16, seed=9)
    cp = RegionalControlPlane(
        rg, regions=4, fanout=0, micro_batch=8, max_attempts=2, seed=0,
        **PYM,
    )
    cp.register_tenant("a", weight=3.0)
    cp.register_tenant("b", weight=1.0)
    rng = np.random.default_rng(0)
    for step in range(25):
        for _ in range(4):  # heavy skew: a floods, b trickles
            df = random_dataflow(rg, 4, seed=7000 + step * 7,
                                 creq_range=(0.1, 0.5),
                                 breq_range=(0.5, 3.0))
            cp.submit("a", df)
        if step % 3 == 0:
            df = random_dataflow(rg, 4, seed=8000 + step,
                                 creq_range=(0.1, 0.5),
                                 breq_range=(0.5, 3.0))
            cp.submit("b", df)
        cp.pump()
        ids = cp.active_ids()
        if ids and step % 2:
            cp.release(int(rng.choice(ids)))
        # the property: per-region committed never exceeds the region's
        # base capacity, residuals never go negative, anywhere, ever
        for r, rcp in enumerate(cp.regions):
            assert np.all(rcp.placer.cap >= -1e-6)
            assert np.all(rcp.placer.bw >= -1e-6)
            held = sum(
                float(np.sum(t.df.creq))
                for t in rcp.placer.tickets.values()
            )
            assert held <= float(np.sum(rcp.placer.base.cap)) + 1e-6
        cp.check_invariants()
    assert cp.engine_stats().gossip_messages == 0  # it really was stale
    assert cp.bus.max_staleness() >= 20  # versions kept advancing unseen


# ---------------------------------------------------------------------------
# multi-hop spanning decomposition
# ---------------------------------------------------------------------------


def _line_plane(R, k=4, seed=0, **kw):
    from repro.core import region_line

    rg, assign = region_line(R, k, seed=seed)
    cp = RegionalControlPlane(rg, regions=R, region_of=assign, seed=seed,
                              **PYM, **kw)
    cp.register_tenant("a", weight=3.0)
    cp.register_tenant("b", weight=1.0)
    return rg, cp


def test_multi_hop_chain_admission_and_release():
    """A dataflow pinned from region 0 to region 3 of a 4-region line —
    previously retry/drop — is admitted over the full region chain by one
    bounded 2PC, and release returns every reservation on every hop."""
    rg, cp = _line_plane(4)
    df = DataflowPath.make([0.0, 0.2, 0.2, 0.2, 0.0], [1.0] * 4,
                           src=0, dst=rg.n - 1)
    rid = cp.submit("a", df)
    (t,) = cp.pump()
    cp.check_invariants()
    assert t.chain == [0, 1, 2, 3]
    assert len(t.parts) == 4 and len(t.cuts) == 3
    assert cp.span_stats["multi_hop"] == 1
    assert cp.span_stats["max_chain"] == 4
    for e, b in zip(t.cuts, t.cut_bws):
        assert cp.cut_residual[e] == pytest.approx(cp.cut_base[e] - b)
    # the documented per-candidate message bound: <= 2 * chain + 2
    s = cp.engine_stats()
    assert s.twopc_messages <= (
        cp.span_stats["attempts"] * cp.max_cut_attempts * (2 * 4 + 2))
    cp.release(rid)
    cp.check_invariants()
    assert all(cp.cut_residual[e] == pytest.approx(cp.cut_base[e])
               for e in cp.cut_base)
    assert all(not c.placer.tickets for c in cp.regions)
    assert cp.conservation()["released"] == 1


def test_non_adjacent_regions_admitted_via_transit():
    """p=2 between regions 0 and 2 of a 3-region line: the middle region
    hosts no dataflow node — its segment is a pure transit reservation
    (ghost gateway endpoints carrying the one cut dataflow edge)."""
    rg, cp = _line_plane(3)
    df = DataflowPath.make([0.1, 0.1], [1.0], src=0, dst=rg.n - 1)
    rid = cp.submit("a", df)
    (t,) = cp.pump()
    cp.check_invariants()
    assert t.chain == [0, 1, 2] and t.splits == [0, 0]
    mid = t.parts[1]
    assert float(np.sum(mid.seg.creq)) == 0.0  # no compute in transit
    # but the transit route's bandwidth IS reserved in the middle region
    tk = cp.regions[1].placer.tickets[mid.tid]
    assert tk.edge_load and all(
        b == pytest.approx(1.0) for b in tk.edge_load.values())
    cp.release(rid)
    cp.check_invariants()
    assert cp.conservation()["released"] == 1


def test_multi_hop_middle_cut_failure_displaces_and_heals():
    rg, cp = _line_plane(4)
    df = DataflowPath.make([0.0, 0.2, 0.2, 0.2, 0.0], [1.0] * 4,
                           src=0, dst=rg.n - 1)
    rid = cp.submit("a", df)
    (t,) = cp.pump()
    middle_cut = t.cuts[1]
    alive, requeued = cp.fail_link(*middle_cut)
    cp.check_invariants()
    assert alive == [] and len(requeued) == 4  # every segment torn down
    led = cp.conservation()
    assert led["active"] == 0 and led["queued"] == 1  # displaced, not dropped
    # while the quotient graph is partitioned, the request keeps waiting
    cp.pump()
    assert cp.conservation()["active"] == 0
    cp.restore_link(*middle_cut)
    out = cp.pump()
    cp.check_invariants()
    assert [s.rid for s in out] == [rid]  # same rid readmitted post-heal
    assert cp.conservation()["active"] == 1


def test_multi_hop_transit_gateway_failure_displaces():
    rg, cp = _line_plane(3)
    df = DataflowPath.make([0.1, 0.1], [1.0], src=0, dst=rg.n - 1)
    rid = cp.submit("a", df)
    (t,) = cp.pump()
    gateway = t.cuts[0][1]  # inbound gateway of the transit region
    cp.fail_node(gateway)
    cp.check_invariants()
    assert rid not in cp.active_ids()
    led = cp.conservation()
    assert led["ok"] and led["dropped"] == 0 and led["queued"] == 1
    assert all(cp.cut_residual[e] == pytest.approx(cp.cut_base[e])
               for e in cp.cut_base)


# ---------------------------------------------------------------------------
# partial-teardown regressions (release / fail on half-dead spans)
# ---------------------------------------------------------------------------


def test_region_dropping_segment_tears_down_whole_span():
    """Regression: churn driven through the INNER region plane (bypassing
    the broker's own displacement pass) drops a spanning segment the
    local plane has no rid for.  The broker must still learn of it
    (on_broker_displace hand-off) and tear down the sibling reservations
    + cut bandwidth instead of leaking them."""
    rg, cp = _line_plane(2)
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    rid = cp.submit("a", DataflowPath.make([0.1, 0.1], [1.0], u, v))
    (t,) = cp.pump()
    part = t.parts[0]
    inner = cp.regions[part.region]
    sibling = t.parts[1]
    # kill the segment's pinned local gateway through the inner plane:
    # the re-map cannot re-place a pinned-down endpoint, so the inner
    # plane DROPS a ticket it holds no rid for — the regression path
    inner.fail_node(int(inner.placer.tickets[part.tid].df.dst))
    assert rid not in cp._span_active  # broker reconciled the drop
    assert sibling.tid not in cp.regions[sibling.region].placer.tickets
    assert all(cp.cut_residual[e] == pytest.approx(cp.cut_base[e])
               for e in cp.cut_base)
    led = cp.conservation()
    assert led["ok"] and led["queued"] == 1 and led["dropped"] == 0
    cp.check_invariants()


def test_release_tolerates_already_dropped_sibling():
    """Regression: ``release`` on a spanning ticket one of whose parts
    already vanished must still release every other part and the cut
    bandwidth (guarded teardown), not raise mid-way and leak."""
    rg, cp = _line_plane(2)
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    rid = cp.submit("a", DataflowPath.make([0.1, 0.1], [1.0], u, v))
    (t,) = cp.pump()
    # simulate a region having lost its local ticket without telling the
    # broker (the pre-fix partial-teardown hazard)
    part = t.parts[0]
    cp.regions[part.region].placer.release(part.tid, reason=None)
    cp.release(rid)  # must not raise
    sibling = t.parts[1]
    assert sibling.tid not in cp.regions[sibling.region].placer.tickets
    assert all(cp.cut_residual[e] == pytest.approx(cp.cut_base[e])
               for e in cp.cut_base)
    assert not cp._span_active and not cp._part_of


def test_displace_span_part_is_idempotent():
    rg, cp = _line_plane(2)
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    cp.submit("a", DataflowPath.make([0.1, 0.1], [1.0], u, v))
    (t,) = cp.pump()
    part = t.parts[0]
    tk = cp.regions[part.region].placer.tickets[part.tid]
    cp.regions[part.region].placer.release(part.tid, reason=None)
    cp._child_displaced(part.region, tk.tid, [tk])
    led1 = cp.conservation()
    cp._child_displaced(part.region, tk.tid, [tk])  # double teardown: no-op
    assert cp.conservation() == led1
    assert led1["queued"] == 1
    cp.check_invariants()


# ---------------------------------------------------------------------------
# multi-hop fuzz matrix
# ---------------------------------------------------------------------------


def _multi_hop_plane(R, seed, fanout=1, k=3):
    from repro.core import region_line

    rg, assign = region_line(R, k, seed=seed)
    cp = RegionalControlPlane(
        rg, regions=R, region_of=assign, micro_batch=6, max_attempts=3,
        seed=seed, fanout=fanout, policy=FairSharePolicy(slack=0.4), **PYM,
    )
    cp.register_tenant("a", weight=3.0)
    cp.register_tenant("b", weight=1.0)
    cp.register_tenant("c", weight=2.0, budget=2.0)

    def df_gen(rng, step):
        # bias toward far-spanning endpoint pairs: half the requests pin
        # src in region 0 and dst in the last region (chain length R)
        if rng.random() < 0.5:
            r1, r2 = 0, R - 1
        else:
            r1, r2 = rng.choice(R, size=2, replace=False)
        src = int(rng.choice(np.nonzero(assign == r1)[0]))
        dst = int(rng.choice(np.nonzero(assign == r2)[0]))
        p = int(rng.integers(2, 6))
        creq = rng.uniform(0.02, 0.15, p).astype(np.float32)
        creq[0] = creq[-1] = 0.0
        breq = rng.uniform(0.5, 2.0, p - 1).astype(np.float32)
        return DataflowPath(creq, breq, src, dst)

    return rg, cp, df_gen


@pytest.mark.parametrize("R", [4, 6])
def test_fuzz_multi_hop_conservation(R):
    """Far-spanning workload on an R-region line: the global ledger, cut
    conservation and spanning-handle integrity hold through adversarial
    interleavings, and chains of >= 3 regions are genuinely exercised."""
    rg, cp, df_gen = _multi_hop_plane(R, seed=R)
    led = _fuzz_plane(cp, rg, seed=R, steps=50, df_gen=df_gen)
    assert led["submitted"] > 0
    assert cp.span_stats["max_chain"] >= 3
    assert cp.span_stats["multi_hop"] >= 1


def test_fuzz_multi_hop_stale_gossip_never_overcommits():
    """fanout=0 (estimates never propagate) on a 4-region line with a
    far-spanning workload: multi-hop 2PC admissions must still never
    exceed any region's own residual — over-commit safety is local
    validation, not estimate freshness, even across chains."""
    rg, cp, df_gen = _multi_hop_plane(4, seed=11, fanout=0)
    _fuzz_plane(cp, rg, seed=11, steps=50, df_gen=df_gen)
    for rcp in cp.regions:
        assert np.all(rcp.placer.cap >= -1e-6)
        assert np.all(rcp.placer.bw >= -1e-6)
        held = sum(float(np.sum(t.df.creq))
                   for t in rcp.placer.tickets.values())
        assert held <= float(np.sum(rcp.placer.base.cap)) + 1e-6
    assert cp.engine_stats().gossip_messages == 0
    assert cp.span_stats["admitted"] > 0  # spans did flow despite staleness


@pytest.mark.slow
@pytest.mark.parametrize("R", [4, 6])
@pytest.mark.parametrize("seed", [2, 3, 4])
def test_fuzz_multi_hop_conservation_extended(R, seed):
    """Slow-lane matrix: more seeds, longer interleavings, staler gossip,
    bigger regions."""
    rg, cp, df_gen = _multi_hop_plane(R, seed=seed, fanout=1, k=4)
    cp.gossip_period = 3
    _fuzz_plane(cp, rg, seed=seed, steps=120, df_gen=df_gen)
    assert cp.span_stats["max_chain"] >= 3


# ---------------------------------------------------------------------------
# accounting / handle-resolution regressions (review findings)
# ---------------------------------------------------------------------------


def test_failed_spanning_probes_are_not_service_rejections():
    """2PC reserve probes that nack must not inflate the regional
    placers' rejected counters (same convention as admit_preempting's
    probes): the spanning outcome is accounted once, by the broker."""
    rg, cp = _regional(max_attempts=3)
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    huge = float(np.sum(rg.cap)) + 1.0  # fits nowhere, ever
    cp.submit("a", DataflowPath.make([0.0, huge, 0.0], [1.0, 1.0], u, v))
    for _ in range(3):
        cp.pump()
    assert cp.span_stats["attempts"] >= 3  # probes really ran
    assert all(c.placer.stats.rejected == 0 for c in cp.regions)
    cp.check_invariants()


def test_owner_region_resolves_local_ticket_handles():
    """In-region handles returned by pump() live in their region's local
    id space; owner_region identifies the owner so the route lifts back
    to global ids through the right view."""
    rg, cp = _regional()
    nodes = np.nonzero(cp.region_of == 1)[0]
    df = DataflowPath.make([0.0, 0.2, 0.0], [1.0, 1.0],
                           int(nodes[0]), int(nodes[-1]))
    cp.submit("a", df)
    (t,) = cp.pump()
    r = cp.owner_region(t)
    assert r == 1
    route_global = [int(cp.views[r].to_global(v)) for v in t.mapping.route]
    assert route_global[0] == df.src and route_global[-1] == df.dst
    assert all(cp.region_of[v] == 1 for v in route_global)
    # a released handle resolves to no region
    cp.release(cp.active_ids()[0])
    assert cp.owner_region(t) is None


def test_facade_dispatches_on_region_of_alone():
    """ControlPlane(rg, region_of=...) must build the regional plane (the
    assignment defines the region count) — not silently ignore the
    partition and leak region_of into the solver config; a contradicting
    explicit regions= fails fast."""
    from repro.core import region_line

    rg, assign = region_line(3, 4, seed=1)
    cp = ControlPlane(rg, region_of=assign, **PYM)
    assert isinstance(cp, RegionalControlPlane) and cp.R == 3
    cp.register_tenant("a")
    cp.submit("a", DataflowPath.make([0.0, 0.1], [1.0], 0, 1))
    cp.pump()  # solver must never see region_of
    cp.check_invariants()
    assert isinstance(
        ControlPlane(rg, regions=3, region_of=assign, **PYM),
        RegionalControlPlane)
    with pytest.raises(ValueError, match="contradicts"):
        ControlPlane(rg, regions=2, region_of=assign, **PYM)


def test_candidate_search_bounded_for_long_dataflows():
    """A long dataflow over a long chain must not enumerate the full
    split-combination space: candidate generation stays fast and still
    yields admissible balanced candidates."""
    import time

    from repro.core import region_line

    rg, assign = region_line(6, 4, seed=2)
    cp = RegionalControlPlane(rg, regions=6, region_of=assign, seed=0, **PYM)
    cp.register_tenant("a")
    p = 120  # C(p+m-2, m) would be ~2e8 at m=5 without the windowing
    creq = np.full(p, 0.01, np.float32)
    creq[0] = creq[-1] = 0.0
    df = DataflowPath(creq, np.full(p - 1, 0.5, np.float32), 0, rg.n - 1)
    chain = cp._region_chain(0, 5)
    t0 = time.perf_counter()
    cands = cp._candidate_chains(df, chain)
    assert time.perf_counter() - t0 < 2.0  # bounded enumeration
    assert cands  # and still productive
    rid = cp.submit("a", df)
    (t,) = cp.pump()
    assert t.rid == rid and len(t.chain) == 6
    cp.check_invariants()


# ---------------------------------------------------------------------------
# congestion gossip (per-cut gateway occupancy estimates)
# ---------------------------------------------------------------------------


def _grid_plane(rows=2, cols=3, k=3, seed=0, **kw):
    from repro.core import region_grid

    rg, assign = region_grid(rows, cols, k, seed=seed)
    cp = RegionalControlPlane(rg, regions=rows * cols, region_of=assign,
                              seed=seed, **PYM, **kw)
    cp.register_tenant("a", weight=3.0)
    cp.register_tenant("b", weight=1.0)
    cp.register_tenant("c", weight=2.0, budget=2.0)
    return rg, assign, cp


def _saturate_cut(cp, r1, r2, leave=0.2):
    """Stand a spanning reservation on the single (r1, r2) cut, leaving
    only ``leave`` residual bandwidth on it."""
    (e,) = cp._cut_by_pair[(r1, r2)]
    u, v = e
    b = cp.cut_residual[e] - leave
    rid = cp.submit("b", DataflowPath.make([0.01, 0.01], [b], src=u, dst=v))
    out = cp.pump()
    assert any(getattr(t, "rid", None) == rid for t in out)
    assert cp.cut_residual[e] == pytest.approx(leave, abs=1e-3)
    return e


def _saturate_region_compute(cp, r, frac=0.95):
    """Fill region ``r``'s nodes to ``frac`` occupancy via direct local
    admissions (bypassing the queues, like the fairness test does)."""
    rcp = cp.regions[r]
    for lv in range(rcp.placer.base.cap.shape[0]):
        take = float(rcp.placer.cap[lv]) - (1.0 - frac) * float(
            rcp.placer.base.cap[lv])
        if take > 0:
            df = DataflowPath.make([take], [], src=lv, dst=lv)
            assert rcp.placer.admit(df, tenant="b") is not None


def test_gossip_carries_congestion_estimates():
    bus = GossipBus(3, fanout=2, seed=0)  # fanout 2 of 2 peers: full push
    rec = bus.publish(0, {}, {}, 1.0, congestion={7: 0.5, 9: 0.25})
    assert rec.congestion == {7: 0.5, 9: 0.25}
    # the wire-size accounting includes the congestion entries
    assert GossipBus._record_size(rec) == 3 + 2
    bus.tick()
    for r in range(3):
        assert bus.congestion_view(r)[7] == 0.5
    # the freshest record per origin wins (no merge across versions)
    bus.publish(0, {}, {}, 1.0, congestion={7: 0.9})
    bus.tick()
    for r in range(3):
        view = bus.congestion_view(r)
        assert view[7] == 0.9 and 9 not in view
    # on key overlap across origins the pessimistic max wins
    bus.publish(1, {}, {}, 1.0, congestion={7: 0.1})
    bus.tick()
    assert bus.congestion_view(2)[7] == 0.9


def test_congestion_view_reflects_remote_gateway_heat():
    """A saturated region's gateway occupancy reaches every other
    region's congestion view through the existing share gossip — and the
    load-aware edge cost prices its cuts up accordingly."""
    rg, assign, cp = _grid_plane(fanout=5)  # full-fanout: 1-round spread
    _saturate_region_compute(cp, 1)
    cp.pump()  # publish + tick
    occ = cp.bus.congestion_view(0)
    hot = cp._gateways_of[1]
    assert hot and all(occ.get(u, 0.0) > 0.5 for u in hot)
    (e,) = cp._cut_by_pair[(0, 1)]
    assert cp._edge_cost(e, occ) > float(rg.lat[e]) * 1.5
    cp.check_invariants()


def test_zero_fanout_keeps_congestion_estimates_local():
    rg, assign, cp = _grid_plane(fanout=0)
    _saturate_region_compute(cp, 1)
    cp.pump()
    occ = cp.bus.congestion_view(0)
    assert occ and all(int(assign[u]) == 0 for u in occ)  # own gateways only


# ---------------------------------------------------------------------------
# congestion-aware k-shortest chain routing
# ---------------------------------------------------------------------------


def test_region_grid_generator_shape():
    from repro.core import region_grid

    rows, cols, k = 2, 3, 3
    rg, assign = region_grid(rows, cols, k, seed=0)
    R = rows * cols
    assert rg.n == R * k
    np.testing.assert_array_equal(assign, np.repeat(np.arange(R), k))
    # fully meshed inside every region
    for r in range(R):
        base = r * k
        for i in range(k):
            for j in range(i + 1, k):
                assert rg.bw[base + i, base + j] > 0
    # the quotient graph is the grid: east + south neighbors only
    pairs = {
        (int(assign[u]), int(assign[v]))
        for (u, v) in rg.edges() if assign[u] != assign[v]
    }
    expect = set()
    for i in range(rows):
        for j in range(cols):
            r = i * cols + j
            if j + 1 < cols:
                expect |= {(r, r + 1), (r + 1, r)}
            if i + 1 < rows:
                expect |= {(r, r + cols), (r + cols, r)}
    assert pairs == expect


def test_yen_chains_distinct_loopless_cheapest_first():
    rg, assign, cp = _grid_plane(chain_k=4)
    chains = cp._region_chains(0, 5, {})
    assert chains[0] == cp._region_chain(0, 5)  # cold: fewest-hop first
    assert len(chains) == len({tuple(c) for c in chains}) >= 2
    for c in chains:
        assert c[0] == 0 and c[-1] == 5 and len(set(c)) == len(c)
        for r1, r2 in zip(c, c[1:]):  # every hop really is adjacent
            assert cp._cut_by_pair.get((r1, r2))
    # chain costs are non-decreasing in rank
    adj = cp._cost_adjacency({})
    costs = [sum(adj[a][b] for a, b in zip(c, c[1:])) for c in chains]
    assert costs == sorted(costs)


def test_congestion_reranks_chains_before_any_probe():
    """Hot gossiped gateways re-rank the fewest-hop chain behind a cold
    bypass purely in the cost model — before any 2PC probe spends budget.
    congestion_weight=0 restores pure-latency ranking."""
    rg, assign, cp = _grid_plane(chain_k=2)
    hot = {u: 1.0
           for e in cp._cut_by_pair[(0, 1)] + cp._cut_by_pair[(1, 2)]
           for u in e}
    chains = cp._region_chains(0, 2, hot)
    assert chains[0] == [0, 3, 4, 5, 2]  # cold bypass ranks first
    assert [0, 1, 2] in chains           # hot fewest-hop still raced
    assert cp._region_chains(0, 2, {})[0] == [0, 1, 2]  # cold: fewest-hop
    cp.congestion_weight = 0.0           # weight 0: occupancy is ignored
    assert cp._region_chains(0, 2, hot)[0] == [0, 1, 2]


def test_gateway_hotspot_k_chain_admits_where_single_chain_collapses():
    """The tentpole regression: stand a reservation on the (0, 1) cut so
    the fewest-hop chain 0-1-2 has no feasible candidate.  The legacy
    single-chain broker burns every attempt on that chain and drops the
    request; the k-chain racer probes the cold bypass 0-3-4-5-2 inside
    the same 2PC budget and admits."""
    results = []
    for chain_k in (1, 2):
        rg, assign, cp = _grid_plane(chain_k=chain_k, max_attempts=3)
        hot = _saturate_cut(cp, 0, 1, leave=0.2)
        dst = int(np.nonzero(assign == 2)[0][-1])
        df = DataflowPath.make([0.0, 0.2, 0.2, 0.0], [1.0] * 3,
                               src=0, dst=dst)
        rid = cp.submit("a", df)
        out = [t for _ in range(3) for t in cp.pump()]
        cp.check_invariants()
        results.append((cp, rid, out, hot))
    cp1, rid1, out1, _ = results[0]
    assert out1 == []  # single-chain: never admitted, dropped
    assert cp1.conservation()["dropped"] == 1
    assert cp1.span_stats["no_cut"] >= 3
    cp2, rid2, out2, hot = results[1]
    (t,) = out2
    # admitted over a >2-hop bypass that avoids the saturated cut
    assert t.rid == rid2
    assert t.chain[0] == 0 and t.chain[-1] == 2 and len(t.chain) == 5
    assert t.chain != [0, 1, 2] and hot not in t.cuts
    assert cp2.span_stats["rerouted"] == 1
    assert cp2.span_stats["multi_hop"] >= 1
    assert cp2.span_stats["max_chain"] == 5
    led = cp2.conservation()
    assert led["ok"] and led["dropped"] == 0
    # racing stayed inside the documented per-candidate message bound
    assert cp2.engine_stats().twopc_messages <= (
        cp2.span_stats["attempts"] * cp2.max_cut_attempts * (2 * 5 + 2))


def test_stale_congestion_misroutes_but_never_overcommits():
    """fanout=0: occupancy estimates never propagate, so the router
    prices remote hot gateways as cold and may well rank the hot chain
    first (a misroute).  The property: ranking is ONLY advisory — every
    admission still 2PC-validates against real residuals, so nothing
    over-commits no matter how wrong the view is."""
    rg, assign, cp = _grid_plane(fanout=0, chain_k=3, max_attempts=3,
                                 micro_batch=6)
    _saturate_region_compute(cp, 1)
    rng = np.random.default_rng(3)
    for step in range(20):
        src = int(rng.choice(np.nonzero(assign == 0)[0]))
        dst = int(rng.choice(np.nonzero(assign == 2)[0]))
        cp.submit("a", DataflowPath.make(
            [0.0, 0.3, 0.3, 0.0], [1.0] * 3, src=src, dst=dst))
        cp.pump()
        # the home region's view holds no region-1 estimates to warn it
        assert all(int(assign[u]) == 0 for u in cp.bus.congestion_view(0))
        for rcp in cp.regions:
            assert np.all(rcp.placer.cap >= -1e-6)
            assert np.all(rcp.placer.bw >= -1e-6)
        assert all(-1e-6 <= cp.cut_residual[e] <= cp.cut_base[e] + 1e-6
                   for e in cp.cut_base)
        cp.check_invariants()
    assert cp.span_stats["admitted"] >= 1  # spans still flowed
    assert cp.bus.max_staleness() >= 10    # and the view really was stale


@pytest.mark.parametrize("seed", [0, 1])
def test_chain_racer_bit_identical_on_unique_chain_topology(seed):
    """Acceptance gate: on a region line (ONE loopless chain per pair,
    one gate per hop) the k-chain racer must collapse to the legacy
    single-chain broker bit for bit — same admissions, same residuals,
    same cut ledger, same stats, step by step under fuzzed ops."""
    from repro.core import region_line

    rg, assign = region_line(4, 3, seed=seed)
    kw = dict(regions=4, region_of=assign, micro_batch=6, max_attempts=3,
              seed=seed, fanout=1, **PYM)
    legacy = RegionalControlPlane(rg, chain_k=1, **kw)
    racer = RegionalControlPlane(rg, chain_k=3, **kw)
    for cp in (legacy, racer):
        cp.register_tenant("a", weight=3.0)
        cp.register_tenant("b", weight=1.0)
    cuts = sorted(legacy.cut_base)
    rng = np.random.default_rng(seed)
    failed: list[tuple[int, int]] = []
    for step in range(50):
        op = rng.choice(
            ["submit", "pump", "release", "partition", "heal"],
            p=[0.35, 0.30, 0.15, 0.10, 0.10],
        )
        if op == "submit":
            r1, r2 = rng.choice(4, size=2, replace=False)
            src = int(rng.choice(np.nonzero(assign == r1)[0]))
            dst = int(rng.choice(np.nonzero(assign == r2)[0]))
            p = int(rng.integers(2, 6))
            creq = rng.uniform(0.02, 0.15, p).astype(np.float32)
            creq[0] = creq[-1] = 0.0
            breq = rng.uniform(0.5, 2.0, p - 1).astype(np.float32)
            df = DataflowPath(creq, breq, src, dst)
            t = str(rng.choice(["a", "b"]))
            assert legacy.submit(t, df) == racer.submit(t, df)
        elif op == "pump":
            assert ([getattr(t, "rid", None) for t in legacy.pump()]
                    == [getattr(t, "rid", None) for t in racer.pump()])
        elif op == "release":
            ids = legacy.active_ids()
            assert ids == racer.active_ids()
            if ids:
                rid = int(rng.choice(ids))
                legacy.release(rid)
                racer.release(rid)
        elif op == "partition" and len(failed) < 2:
            e = cuts[int(rng.integers(0, len(cuts)))]
            if e not in failed:
                legacy.fail_link(*e)
                racer.fail_link(*e)
                failed.append(e)
        elif op == "heal" and failed:
            e = failed.pop(int(rng.integers(0, len(failed))))
            legacy.restore_link(*e)
            racer.restore_link(*e)
        assert legacy.cut_residual == racer.cut_residual
        for c1, c2 in zip(legacy.regions, racer.regions):
            np.testing.assert_array_equal(c1.placer.cap, c2.placer.cap)
            np.testing.assert_array_equal(c1.placer.bw, c2.placer.bw)
        assert legacy.conservation() == racer.conservation()
        assert legacy.span_stats == racer.span_stats
        legacy.check_invariants()
        racer.check_invariants()
    assert legacy.span_stats["admitted"] > 0  # the fuzz exercised spans
    assert racer.span_stats["rerouted"] == 0  # nothing to reroute to


def test_displacement_livelock_budget_eventually_drops():
    """Regression: a spanning request ping-ponging between admission and
    displacement used to reset its attempt budget on every displacement —
    livelocking forever.  The cumulative budget (max_cum_attempts) now
    drops it, visibly, after bounded work."""
    rg, cp = _line_plane(2, max_cum_attempts=3)
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    rid = cp.submit("a", DataflowPath.make([0.1, 0.1], [1.0], u, v))
    (t,) = cp.pump()
    for i in range(3):
        assert cp.fail_link(*t.cut)[1]  # displaced every time
        cp.restore_link(*t.cut)
        out = cp.pump()
        if i < 2:  # budget not yet spent: readmitted, same rid
            assert [s.rid for s in out] == [rid]
            (t,) = out
        else:      # the third displacement spent the cumulative budget
            assert out == []
    assert cp.span_stats["livelock_dropped"] == 1
    assert cp.span_stats["max_req_attempts"] == 3
    led = cp.conservation()
    assert led["ok"] and led["dropped"] == 1 and led["active"] == 0
    assert all(cp.cut_residual[e] == pytest.approx(cp.cut_base[e])
               for e in cp.cut_base)
    cp.check_invariants()


def test_attempts_admitted_counted_once():
    """Accounting regression: attempts is counted once per
    _try_place_spanning entry, admitted once per 2PC commit — neither is
    double-counted between the pump drain and the broker interface."""
    rg, cp = _regional()
    rid = cp.submit("a", _spanning_df(cp))
    cp.pump()
    assert cp.span_stats["attempts"] == 1 and cp.span_stats["admitted"] == 1
    cp.release(rid)
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    huge = float(np.sum(rg.cap)) + 1.0
    cp.submit("a", DataflowPath.make([0.0, huge, 0.0], [1.0, 1.0], u, v))
    cp.pump()  # a failing attempt counts attempts but not admitted
    assert cp.span_stats["attempts"] == 2 and cp.span_stats["admitted"] == 1
    cp.check_invariants()


# ---------------------------------------------------------------------------
# cut-ledger coherence regressions (fail/restore, half-dead spans)
# ---------------------------------------------------------------------------


def test_cut_fail_restore_idempotent_and_restores_full_residual():
    """Double fail / double restore of a cut under a standing span: the
    teardown returns the cut bandwidth exactly once, the healed cut
    reappears with its full base residual in both directions, and the
    displaced request is readmitted."""
    rg, cp = _line_plane(3)
    df = DataflowPath.make([0.0, 0.2, 0.2, 0.0], [1.0] * 3,
                           src=0, dst=rg.n - 1)
    rid = cp.submit("a", df)
    (t,) = cp.pump()
    e = t.cuts[0]
    cp.fail_link(*e)
    cp.fail_link(*e)  # idempotent: nothing left to displace or return
    cp.check_invariants()
    assert all(-1e-6 <= cp.cut_residual[c] <= cp.cut_base[c] + 1e-6
               for c in cp.cut_base)
    assert cp.cut_residual[e] == pytest.approx(cp.cut_base[e])
    assert not cp.cut_link_up[e]
    assert cp._region_chain(0, 2) is None  # quotient graph partitioned
    cp.restore_link(*e)
    cp.restore_link(*e)  # idempotent
    assert cp.cut_link_up[e] and cp.cut_link_up[(e[1], e[0])]
    assert cp.cut_residual[e] == pytest.approx(cp.cut_base[e])
    out = cp.pump()
    assert [s.rid for s in out] == [rid]
    cp.check_invariants()


def test_restore_never_failed_cut_does_not_inflate_residual():
    """restore_link on a healthy cut carrying a live reservation must be
    a no-op on the ledger: residual stays base - reserved (a heal never
    mints bandwidth)."""
    rg, cp = _line_plane(2)
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    cp.submit("a", DataflowPath.make([0.1, 0.1], [1.0], u, v))
    (t,) = cp.pump()
    before = dict(cp.cut_residual)
    cp.restore_link(*t.cut)
    assert cp.cut_residual == before
    assert cp.cut_residual[t.cut] == pytest.approx(cp.cut_base[t.cut] - 1.0)
    cp.check_invariants()


def test_half_dead_span_fail_link_returns_cut_bandwidth_once():
    """A region silently losing its segment (placer-level release, no
    broker hand-off) followed by a cut failure: the span teardown must
    return the cut bandwidth exactly once — residual == base, never
    above it."""
    rg, cp = _line_plane(2)
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    cp.submit("a", DataflowPath.make([0.1, 0.1], [1.0], u, v))
    (t,) = cp.pump()
    part = t.parts[0]
    cp.regions[part.region].placer.release(part.tid, reason=None)
    cp.fail_link(*t.cut)
    assert cp.cut_residual[t.cut] == pytest.approx(cp.cut_base[t.cut])
    assert all(cp.cut_residual[c] <= cp.cut_base[c] + 1e-6
               for c in cp.cut_base)
    cp.check_invariants()
    assert cp.conservation()["ok"]


def test_release_of_displaced_request_raises_like_centralized():
    """release() of a rid that was displaced back to a queue (not
    active) is a caller bug and raises KeyError — the same contract as
    the centralized plane — and must not corrupt the ledger."""
    rg, cp = _line_plane(2)
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    rid = cp.submit("a", DataflowPath.make([0.1, 0.1], [1.0], u, v))
    (t,) = cp.pump()
    cp.fail_link(*t.cut)
    with pytest.raises(KeyError):
        cp.release(rid)
    led = cp.conservation()
    assert led["ok"] and led["queued"] == 1
    cp.restore_link(*t.cut)
    out = cp.pump()
    assert [s.rid for s in out] == [rid]
    cp.check_invariants()
