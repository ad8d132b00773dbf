"""Online multi-request placement service: admission, residual-capacity
invariants, micro-batched solving, churn re-mapping, and the pipelined
(dispatch/commit-split) admission path."""
import dataclasses

import numpy as np
import pytest

from repro.core import (
    AdmissionPipeline,
    DataflowPath,
    OnlinePlacer,
    ResourceGraph,
    random_dataflow,
    validate_mapping,
    waxman,
)


def _light_requests(rg, k, p=5, seed0=500):
    return [
        random_dataflow(rg, p, seed=seed0 + i,
                        creq_range=(0.02, 0.1), breq_range=(0.5, 3.0))
        for i in range(k)
    ]


def test_admit_release_roundtrip():
    rg = waxman(16, seed=2)
    placer = OnlinePlacer(rg)
    df = _light_requests(rg, 1)[0]
    t = placer.admit(df)
    assert t is not None
    ok, why = validate_mapping(rg, df, t.mapping)
    assert ok, why
    assert np.sum(placer.cap) < np.sum(rg.cap)  # capacity committed
    placer.check_invariants()
    placer.release(t)
    np.testing.assert_allclose(placer.cap, rg.cap.astype(np.float64))
    np.testing.assert_allclose(placer.bw, rg.bw.astype(np.float64))
    placer.check_invariants()


def test_admit_many_64_concurrent_with_invariants():
    """The acceptance-criteria scenario: >= 64 concurrent requests admitted
    against residual capacity, invariants intact throughout."""
    rg = waxman(24, seed=7)
    placer = OnlinePlacer(rg)
    dfs = _light_requests(rg, 80)
    tickets = []
    for i in range(0, len(dfs), 32):
        tickets.extend(placer.admit_many(dfs[i:i + 32]))
        placer.check_invariants()
    admitted = [t for t in tickets if t is not None]
    assert len(admitted) >= 64, len(admitted)
    # every committed mapping was feasible on the network it was granted
    assert placer.stats.admitted == len(admitted)
    # aggregate commitments really left the residual
    total_creq = sum(float(np.sum(t.df.creq)) for t in admitted)
    assert np.sum(rg.cap) - np.sum(placer.cap) == pytest.approx(total_creq, rel=1e-6)


def test_admission_rejects_when_capacity_exhausted():
    # tiny network, big requests: the second identical request can't fit
    rg = ResourceGraph.from_edge_list(
        [0.0, 2.0, 0.0], [(0, 1, 50.0, 1.0), (1, 2, 50.0, 1.0)]
    )
    df = DataflowPath.make([0.0, 2.0, 0.0], [5.0, 5.0], src=0, dst=2)
    placer = OnlinePlacer(rg)
    assert placer.admit(df) is not None
    assert placer.admit(df) is None  # node 1 has no residual capacity left
    assert placer.stats.rejected == 1
    placer.check_invariants()


def test_bandwidth_is_committed_too():
    rg = ResourceGraph.from_edge_list(
        [0.0, 5.0, 0.0], [(0, 1, 10.0, 1.0), (1, 2, 10.0, 1.0)]
    )
    df = DataflowPath.make([0.0, 1.0, 0.0], [8.0, 8.0], src=0, dst=2)
    placer = OnlinePlacer(rg)
    assert placer.admit(df) is not None
    # links now hold 2 GB/s residual < 8 required -> reject
    assert placer.admit(df) is None
    placer.check_invariants()


def test_batched_admission_matches_sequential_costs():
    rg = waxman(20, seed=11)
    dfs = _light_requests(rg, 12, seed0=900)
    seq = OnlinePlacer(rg)
    bat = OnlinePlacer(rg)
    t_seq = [seq.admit(d) for d in dfs]
    t_bat = bat.admit_many(dfs)
    for a, b in zip(t_seq, t_bat):
        assert (a is None) == (b is None)
        if a is not None:
            assert abs(a.mapping.cost - b.mapping.cost) < 1e-3
    seq.check_invariants()
    bat.check_invariants()


def test_node_churn_remaps_displaced():
    rg = waxman(24, seed=3)
    placer = OnlinePlacer(rg)
    tickets = [t for t in placer.admit_many(_light_requests(rg, 24)) if t]
    assert tickets
    # fail the most-used intermediate node
    counts = {}
    for t in tickets:
        for v in t.mapping.route:
            if v not in (t.df.src, t.df.dst):
                counts[v] = counts.get(v, 0) + 1
    assert counts, "no intermediate nodes used; instance too easy"
    victim = max(counts, key=counts.get)
    displaced_before = counts[victim]
    remapped, dropped = placer.fail_node(victim)
    assert len(remapped) + len(dropped) >= displaced_before
    placer.check_invariants()
    # no surviving placement routes through the failed node
    for t in placer.tickets.values():
        assert victim not in t.mapping.route
    # re-admitted mappings are valid on the degraded network
    degraded = placer.residual_graph()
    assert degraded.cap[victim] == 0.0
    for t in remapped:
        assert victim not in t.mapping.route


def test_link_churn_remaps_displaced():
    rg = waxman(20, seed=9)
    placer = OnlinePlacer(rg)
    tickets = [t for t in placer.admit_many(_light_requests(rg, 16, seed0=700)) if t]
    multi_hop = [t for t in tickets if len(t.mapping.route) > 1]
    assert multi_hop
    u, v = next(iter(multi_hop[0].edge_load))
    placer.fail_link(u, v)
    placer.check_invariants()
    for t in placer.tickets.values():
        assert (u, v) not in t.edge_load and (v, u) not in t.edge_load


def test_src_down_rejects():
    rg = waxman(16, seed=6)
    placer = OnlinePlacer(rg)
    df = _light_requests(rg, 1, seed0=42)[0]
    placer.fail_node(df.src)
    assert placer.admit(df) is None
    placer.restore_node(df.src)
    assert placer.admit(df) is not None
    placer.check_invariants()


def test_micro_batch_bucketing_bounds_jit_recompiles():
    """``admit_many`` buckets the DP batch to the next power of two, so a
    churny stream of distinct micro-batch sizes compiles at most
    log2(max batch) specializations of the vmapped DP — not one per size.
    Counted directly in the jit cache of the shared vmapped driver."""
    from repro.core import leastcost as lc

    lc._vmapped_dp.cache_clear()
    rg = waxman(12, seed=3)
    placer = OnlinePlacer(rg)  # leastcost_jax: the natively-batching path
    p = 5
    sizes = [1, 2, 3, 4, 5, 6, 7, 8, 3, 5, 7, 2, 6, 1, 8, 4]
    assert len(set(sizes)) == 8  # 8 distinct arrival sizes...
    for j, b in enumerate(sizes):
        dfs = [
            random_dataflow(rg, p, seed=900 + 37 * j + i,
                            creq_range=(0.01, 0.05),
                            breq_range=(0.2, 1.0))
            for i in range(b)
        ]
        for t in placer.admit_many(dfs):
            if t is not None:
                placer.release(t)  # keep capacity churn-free
        placer.check_invariants()
    # one (n, p, max_rounds) driver served every batch...
    assert lc._vmapped_dp.cache_info().currsize == 1
    fn = lc._vmapped_dp(rg.n, p, rg.n - 1)
    # ...with only power-of-two batch specializations: {1, 2, 4, 8}
    assert fn._cache_size() <= 4, fn._cache_size()


# ---------------------------------------------------------------------------
# pipelined admission: dispatch/commit split, staleness fencing, warmup
# ---------------------------------------------------------------------------


def _clock_free(stats):
    """Stats minus the wall-clock fields (the only legitimate divergence
    between the synchronous and the depth-1 pipelined path)."""
    d = dataclasses.asdict(stats)
    for k in ("solve_ms", "overhead_ms", "conflict_resolve_ms",
              "dp_wait_ms", "reconstruct_ms", "remap_ms"):
        d.pop(k)
    return d


@pytest.mark.parametrize("seed", [0, 1])
def test_pipeline_depth1_bit_identical_to_sync(seed):
    """Fuzzed op interleavings: AdmissionPipeline(depth=1) is the synchronous
    ``admit_many`` path — same tickets (tid, assignment, cost), bitwise-same
    residuals, identical stats up to wall clock.  Same pattern as the R=1
    regional identity fuzz."""
    rng = np.random.default_rng(seed)
    rg = waxman(12, seed=5)
    a = OnlinePlacer(rg)
    b = OnlinePlacer(rg)
    pipe = AdmissionPipeline(b, depth=1)
    failed_nodes: list[int] = []
    failed_links: list[tuple[int, int]] = []
    edges = list(rg.edges())

    for step in range(40):
        op = rng.choice(
            ["admit", "release", "fail_node", "restore_node",
             "fail_link", "restore_link"],
            p=[0.45, 0.20, 0.10, 0.10, 0.075, 0.075],
        )
        if op == "admit":
            dfs = [
                random_dataflow(rg, 4, seed=1000 * seed + 13 * step + i,
                                creq_range=(0.05, 0.2),
                                breq_range=(0.5, 2.0))
                for i in range(int(rng.integers(1, 5)))
            ]
            ta = a.admit_many(dfs)
            out = pipe.push(dfs)
            assert len(out) == 1  # depth=1: every push commits in-line
            for x, y in zip(ta, out[0][1]):
                assert (x is None) == (y is None)
                if x is not None:
                    assert x.tid == y.tid
                    assert x.mapping.assign == y.mapping.assign
                    assert x.mapping.cost == y.mapping.cost
        elif op == "release" and a.tickets:
            tid = int(rng.choice(sorted(a.tickets)))
            a.release(tid)
            b.release(tid)
        elif op == "fail_node" and len(failed_nodes) < 2:
            v = int(rng.integers(0, rg.n))
            if v not in failed_nodes:
                rem_a, drop_a = a.fail_node(v)
                rem_b, drop_b = b.fail_node(v)
                assert [t.tid for t in rem_a] == [t.tid for t in rem_b]
                assert [t.tid for t in drop_a] == [t.tid for t in drop_b]
                failed_nodes.append(v)
        elif op == "restore_node" and failed_nodes:
            v = failed_nodes.pop(int(rng.integers(0, len(failed_nodes))))
            a.restore_node(v)
            b.restore_node(v)
        elif op == "fail_link" and len(failed_links) < 2:
            u, v = edges[int(rng.integers(0, len(edges)))]
            a.fail_link(u, v)
            b.fail_link(u, v)
            failed_links.append((u, v))
        elif op == "restore_link" and failed_links:
            u, v = failed_links.pop(int(rng.integers(0, len(failed_links))))
            a.restore_link(u, v)
            b.restore_link(u, v)
        # bit-identical residual state after EVERY op
        assert sorted(a.tickets) == sorted(b.tickets)
        assert np.array_equal(a.cap, b.cap)
        assert np.array_equal(a.bw, b.bw)
        a.check_invariants()
        b.check_invariants()

    assert _clock_free(a.stats) == _clock_free(b.stats)
    assert b.stats.stale_batches == 0  # depth=1 can never go stale


def test_churn_mid_pipeline_displaces_exactly_as_sync():
    """``fail_node`` while a batch is in flight: the epoch fence discards the
    stale optimistic solve and the commit re-solves fresh, so the pipelined
    placer lands in exactly the synchronous placer's state."""
    rg = waxman(16, seed=2)
    a = OnlinePlacer(rg)
    b = OnlinePlacer(rg)
    base = _light_requests(rg, 8)
    a.admit_many(base)
    b.admit_many(base)
    batch = _light_requests(rg, 4, seed0=900)
    pending = b.dispatch_admit(batch)  # optimistic, pre-churn snapshot

    counts: dict[int, int] = {}
    for t in a.tickets.values():
        for v in t.mapping.route:
            if v not in (t.df.src, t.df.dst):
                counts[v] = counts.get(v, 0) + 1
    assert counts, "no intermediate nodes used; instance too easy"
    victim = max(counts, key=counts.get)
    rem_a, drop_a = a.fail_node(victim)
    rem_b, drop_b = b.fail_node(victim)
    assert [t.tid for t in rem_a] == [t.tid for t in rem_b]
    assert [t.tid for t in drop_a] == [t.tid for t in drop_b]

    ta = a.admit_many(batch)  # sync path solves on the degraded network
    tb = b.commit_admit(pending)  # stale path must reach the same result
    assert b.stats.stale_batches == 1
    for x, y in zip(ta, tb):
        assert (x is None) == (y is None)
        if x is not None:
            assert x.mapping.assign == y.mapping.assign
            assert x.mapping.cost == y.mapping.cost
    assert np.array_equal(a.cap, b.cap)
    assert np.array_equal(a.bw, b.bw)
    for t in b.tickets.values():
        assert victim not in t.mapping.route
    a.check_invariants()
    b.check_invariants()


def test_restore_invalidates_in_flight_batch():
    """``restore()`` while a batch is in flight must *invalidate* the stale
    solve (epoch fence), not let it commit against the rolled-back residual:
    the batch was solved on capacity the restore takes away again."""
    rg = waxman(16, seed=4)
    placer = OnlinePlacer(rg)
    # one big standing ticket, snapshotted in
    big = DataflowPath.make(
        [0.0] + [0.3] * 3 + [0.0], [2.0] * 4,
        src=int(_light_requests(rg, 1)[0].src),
        dst=int(_light_requests(rg, 1)[0].dst),
    )
    t_big = placer.admit(big)
    assert t_big is not None
    snap = placer.snapshot()
    epoch_before = placer.epoch

    placer.release(t_big)  # frees capacity the in-flight solve will see
    pending = placer.dispatch_admit(_light_requests(rg, 4, seed0=901))
    placer.restore(snap)  # roll back: the big ticket holds again
    assert placer.epoch > epoch_before  # monotone — never rewound

    tickets = placer.commit_admit(pending)
    # the whole batch was discarded by the fence and re-solved fresh —
    # NOT committed, NOT salvaged via per-request conflict re-solves
    assert placer.stats.stale_batches == 1
    assert placer.stats.batch_conflicts == 0
    assert t_big.tid in placer.tickets
    # whatever the fresh re-solve admitted is live and accounted for
    assert all(t.tid in placer.tickets for t in tickets if t is not None)
    placer.check_invariants()


def test_commit_admit_rejects_double_commit():
    rg = waxman(12, seed=5)
    placer = OnlinePlacer(rg)
    pending = placer.dispatch_admit(_light_requests(rg, 2))
    placer.commit_admit(pending)
    with pytest.raises(AssertionError):
        placer.commit_admit(pending)


def test_warmup_precompiles_every_bucket_and_commits_nothing():
    """``warmup(max_batch=8)`` compiles the single-request shape plus the
    {1,2,4,8} buckets up front; subsequent admissions of any size hit the
    cache, and the warmup itself leaves no trace in residuals or stats."""
    from repro.core import leastcost as lc

    lc._vmapped_dp.cache_clear()
    rg = waxman(12, seed=3)
    placer = OnlinePlacer(rg)
    warm_max = placer.warmup(max_batch=8, p=5)
    assert warm_max == 8
    # nothing committed, nothing counted
    np.testing.assert_array_equal(placer.cap, rg.cap.astype(np.float64))
    assert placer.stats.batches == 0 and placer.stats.solves == 0
    # two vmapped variants: the cold fixpoint DP plus the warm-seeded
    # bounded-correction specialization (tier-2 fast path)
    assert lc._vmapped_dp.cache_info().currsize == 2
    fn = lc._vmapped_dp(rg.n, 5, rg.n - 1, False)
    assert fn._cache_size() == 4, fn._cache_size()  # {1, 2, 4, 8}
    fnw = lc._vmapped_dp(rg.n, 5, placer.max_correction_supersteps, True)
    assert fnw._cache_size() == 4, fnw._cache_size()

    for b in (1, 3, 5, 8):  # non-power-of-two sizes bucket up
        dfs = [
            random_dataflow(rg, 5, seed=40 + 10 * b + i,
                            creq_range=(0.01, 0.05), breq_range=(0.2, 1.0))
            for i in range(b)
        ]
        placer.admit_many(dfs)
    assert lc._vmapped_dp.cache_info().currsize == 2
    assert fn._cache_size() == 4  # no new specializations
    placer.check_invariants()
