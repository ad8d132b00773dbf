"""Compacted solve substrate property suite.

Fuzzes the global<->local bijection of ``core.compact.CompactedView``
end to end: round-trip identity, equivalence of view-compacted solves
with the legacy masked-subgraph solves, residual *write-through*
conservation (locally-sized placers re-assemble the global network
exactly), view invalidation on churn, and the empty-region error paths
the regional plane guards against.
"""
import numpy as np
import pytest

from repro.core import (
    CompactedView,
    DataflowPath,
    OnlinePlacer,
    compact_view,
    random_dataflow,
    region_line,
    solve,
    solve_batch,
    waxman,
)
from repro.core.problem import stack_requests
from repro.service import (
    RegionalControlPlane,
    partition_regions,
    region_subgraph,
    validate_region_of,
)

PYM = dict(method="leastcost_python")


# ---------------------------------------------------------------------------
# bijection round trips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bijection_round_trip_fuzz(seed):
    rng = np.random.default_rng(seed)
    rg = waxman(10 + 2 * seed, seed=seed)
    R = int(rng.integers(2, 5))
    assign = partition_regions(rg, R, seed=seed)
    covered = np.zeros(rg.n, bool)
    for r in range(R):
        v = compact_view(rg, assign, r)
        members = np.nonzero(assign == r)[0]
        # local -> global -> local is the identity on the local space
        loc = np.arange(v.n_local)
        np.testing.assert_array_equal(v.to_local(v.to_global(loc)), loc)
        # global -> local -> global is the identity on the member set
        np.testing.assert_array_equal(v.to_global(v.to_local(members)), members)
        assert all(v.contains(int(g)) for g in members)
        covered[members] = True
        # foreign ids raise, never mask
        foreign = np.nonzero(assign != r)[0]
        if foreign.size:
            with pytest.raises(ValueError):
                v.to_local(int(foreign[0]))
        # df round trip re-pins endpoints and shares the requirements
        df = DataflowPath.make([0.1, 0.2], [1.0],
                               int(members[0]), int(members[-1]))
        ldf = v.compact_df(df)
        rdf = v.uncompact_df(ldf)
        assert (rdf.src, rdf.dst) == (df.src, df.dst)
        assert ldf.creq is df.creq and ldf.breq is df.breq
    assert covered.all()  # views partition the node set


def test_compact_graph_slices_match_masked_subgraph():
    rg = waxman(14, seed=3)
    assign = partition_regions(rg, 3, seed=1)
    for r in range(3):
        v = compact_view(rg, assign, r)
        sub = region_subgraph(rg, assign, r)  # masked, global ids
        g = v.graph()
        assert g.n == v.n_local == int(np.sum(assign == r))
        ix = np.ix_(v.nodes, v.nodes)
        np.testing.assert_array_equal(g.cap, sub.cap[v.nodes])
        np.testing.assert_array_equal(g.bw, sub.bw[ix])
        np.testing.assert_array_equal(g.lat, sub.lat[ix])


def test_identity_view_translations_return_same_objects():
    """The R=1 bit-identity hook: the identity view never copies."""
    rg = waxman(9, seed=0)
    v = CompactedView.identity(rg)
    assert v.is_identity
    assert v.graph() is rg and v.compact_graph(rg) is rg
    df = random_dataflow(rg, 3, seed=1)
    assert v.compact_df(df) is df and v.uncompact_df(df) is df
    m, _ = solve(rg, df, **PYM)
    if m is not None:
        assert v.uncompact_mapping(m) is m and v.compact_mapping(m) is m


def test_empty_region_and_bad_assignment_raise_clear_errors():
    rg = waxman(6, seed=0)
    assign = np.array([0, 0, 0, 2, 2, 2])  # region 1 empty (gap)
    with pytest.raises(ValueError, match="empty"):
        compact_view(rg, assign, 1)
    with pytest.raises(ValueError, match="empty"):
        validate_region_of(rg, assign)
    with pytest.raises(ValueError, match="shape"):
        validate_region_of(rg, [0, 1])
    with pytest.raises(ValueError, match="empty"):
        RegionalControlPlane(rg, regions=3, region_of=assign, **PYM)
    from repro.core import ResourceGraph

    empty = ResourceGraph(np.zeros(0, np.float32),
                          np.zeros((0, 0), np.float32),
                          np.zeros((0, 0), np.float32))
    with pytest.raises(ValueError, match="empty"):
        partition_regions(empty, 2)
    # partition_regions itself never yields an empty region
    for n, R, seed in [(5, 4, 0), (7, 7, 1), (12, 5, 2), (4, 9, 3)]:
        a = partition_regions(waxman(n, seed=seed), R, seed=seed)
        counts = np.bincount(a)
        assert counts.min() >= 1


# ---------------------------------------------------------------------------
# solve equivalence: compacted view vs masked global subgraph
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_solve_through_view_matches_masked_subgraph_solve(seed):
    """engine.solve(view=...) must behave exactly like solving on the
    legacy masked global subgraph, with the mapping lifted back to global
    ids — same feasibility, same cost, same assignment."""
    rg = waxman(15, seed=seed)
    assign = partition_regions(rg, 3, seed=seed)
    rng = np.random.default_rng(seed)
    checked = 0
    for r in range(3):
        v = compact_view(rg, assign, r)
        sub = region_subgraph(rg, assign, r)
        members = np.nonzero(assign == r)[0]
        if members.size < 2:
            continue
        for _ in range(6):
            s, d = rng.choice(members, size=2, replace=False)
            p = int(rng.integers(2, 5))
            creq = rng.uniform(0.05, 0.4, p).astype(np.float32)
            breq = rng.uniform(0.5, 3.0, p - 1).astype(np.float32)
            df = DataflowPath(creq, breq, int(s), int(d))
            mv, stv = solve(rg, df, view=v, **PYM)
            mm, stm = solve(sub, df, **PYM)
            assert (mv is None) == (mm is None)
            assert stv.solve_n == v.n_local and stm.solve_n == rg.n
            if mv is not None:
                assert mv.assign == mm.assign and mv.route == mm.route
                assert mv.cost == pytest.approx(mm.cost)
                checked += 1
    assert checked >= 3  # the fuzz actually exercised feasible solves


def test_solve_batch_through_view_lifts_all_mappings():
    rg = waxman(12, seed=4)
    assign = partition_regions(rg, 2, seed=0)
    v = compact_view(rg, assign, 0)
    members = np.nonzero(assign == 0)[0]
    dfs = [
        DataflowPath.make([0.0, 0.2, 0.0], [1.0, 1.0],
                          int(members[i]), int(members[-1 - i]))
        for i in range(2)
    ]
    ms_v, st = solve_batch(rg, dfs, view=v, **PYM)
    sub = region_subgraph(rg, assign, 0)
    ms_m, _ = solve_batch(sub, dfs, **PYM)
    assert st.solve_n == v.n_local
    for a, b in zip(ms_v, ms_m):
        assert (a is None) == (b is None)
        if a is not None:
            assert a.assign == b.assign and a.cost == pytest.approx(b.cost)


def test_view_aware_tensors_pad_to_local_n():
    """The DP/kernel tensor stack built through a view is n_r-sized —
    the VMEM/HBM footprint claim of the compacted substrate."""
    rg = waxman(16, seed=2)
    assign = partition_regions(rg, 4, seed=0)
    v = compact_view(rg, assign, 0)
    members = np.nonzero(assign == 0)[0]
    df = DataflowPath.make([0.0, 0.1, 0.0], [1.0, 1.0],
                           int(members[0]), int(members[-1]))
    tensors, _ = stack_requests(rg, [df], view=v)
    assert tensors["cap"].shape == (v.n_local,)
    assert tensors["bw"].shape == (v.n_local, v.n_local)
    assert tensors["lat"].shape == (v.n_local, v.n_local)
    assert int(tensors["src"][0]) == v.to_local(df.src)


# ---------------------------------------------------------------------------
# residual write-through conservation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_write_through_conservation_fuzz(seed):
    """Locally-sized per-region placers, driven through admit/release
    churn, must re-assemble the *global* base network exactly when their
    residuals and ticket loads are lifted through the views."""
    rg = waxman(14, seed=seed)
    assign = partition_regions(rg, 3, seed=seed)
    views = [compact_view(rg, assign, r) for r in range(3)]
    placers = [OnlinePlacer(rg, view=v, **PYM) for v in views]
    for p, v in zip(placers, views):
        assert p.base.n == v.n_local  # state is locally sized
    rng = np.random.default_rng(seed)
    live: list[tuple[int, int]] = []
    for step in range(40):
        r = int(rng.integers(0, 3))
        members = np.nonzero(assign == r)[0]
        if rng.random() < 0.65 or not live:
            s, d = rng.choice(members, size=2, replace=False)
            p = int(rng.integers(2, 4))
            df = DataflowPath(
                rng.uniform(0.02, 0.2, p).astype(np.float32),
                rng.uniform(0.5, 2.0, p - 1).astype(np.float32),
                int(s), int(d))
            t = placers[r].admit(views[r].compact_df(df))
            if t is not None:
                live.append((r, t.tid))
        else:
            rr, tid = live.pop(int(rng.integers(0, len(live))))
            placers[rr].release(tid)
        # write-through: global residual + global loads == global base
        cap = np.zeros(rg.n)
        bw = np.zeros((rg.n, rg.n))
        in_region = np.zeros((rg.n, rg.n), bool)
        for pl, v in zip(placers, views):
            cap += v.uncompact_node_vec(pl.cap)
            bw += v.uncompact_link_mat(pl.bw)
            in_region |= v.uncompact_link_mat(
                np.ones((v.n_local, v.n_local), bool))
            for t in pl.tickets.values():
                for gv, c in v.uncompact_node_load(t.node_load).items():
                    cap[gv] += c
                for (gu, gv), b in v.uncompact_edge_load(t.edge_load).items():
                    bw[gu, gv] += b
        np.testing.assert_allclose(cap, rg.cap, atol=1e-4)
        np.testing.assert_allclose(bw[in_region], rg.bw[in_region], atol=1e-4)
        for pl in placers:
            pl.check_invariants()
    assert any(pl.stats.admitted for pl in placers)


def test_placer_solve_sizes_are_region_local():
    rg = waxman(20, seed=5)
    assign = partition_regions(rg, 4, seed=1)
    v = compact_view(rg, assign, 0)
    pl = OnlinePlacer(rg, view=v, **PYM)
    members = np.nonzero(assign == 0)[0]
    df = DataflowPath.make([0.0, 0.1], [1.0], int(members[0]), int(members[1]))
    pl.admit(v.compact_df(df))
    assert pl.stats.solves == 1
    assert pl.stats.mean_solve_n == v.n_local  # n_r, not the global 20


# ---------------------------------------------------------------------------
# view invalidation on churn
# ---------------------------------------------------------------------------


def test_view_invalidation_on_churn():
    """Node/link churn bumps the owning region's bijection generation;
    cut-link churn touches no region's slice (broker ledger only)."""
    rg, assign = region_line(3, 4, seed=2)
    cp = RegionalControlPlane(rg, regions=3, region_of=assign, seed=0, **PYM)
    cp.register_tenant("a")
    v0 = [v.version for v in cp.views]
    victim = 1  # in region 0
    r = int(cp.region_of[victim])
    cp.fail_node(victim)
    assert cp.views[r].version == v0[r] + 1
    assert all(cp.views[q].version == v0[q] for q in range(3) if q != r)
    cp.restore_node(victim)
    assert cp.views[r].version == v0[r] + 2
    # in-region link churn invalidates too
    cp.fail_link(0, 1)
    assert cp.views[0].version == v0[0] + 3
    cp.restore_link(0, 1)
    # cut-link churn is broker business: no view generation changes
    before = [v.version for v in cp.views]
    (cut, _) = sorted(cp.cut_base)[0], None
    cp.fail_link(*sorted(cp.cut_base)[0])
    cp.restore_link(*sorted(cp.cut_base)[0])
    assert [v.version for v in cp.views] == before
    cp.check_invariants()


def test_span_parts_record_bijection_version():
    """Spanning reservations carry the generation they were minted under;
    churn elsewhere in the region bumps the view, making staleness
    detectable (version strictly below current)."""
    rg, assign = region_line(2, 4, seed=0)
    cp = RegionalControlPlane(rg, regions=2, region_of=assign, seed=0, **PYM)
    cp.register_tenant("a")
    (u, v) = max(cp.cut_base, key=cp.cut_base.get)
    rid = cp.submit("a", DataflowPath.make([0.1, 0.1], [1.0], u, v))
    (t,) = cp.pump()
    assert all(
        p.version == cp.views[p.region].version for p in t.parts)
    # churn a non-gateway node in part 0's region: the view generation
    # advances past the part's recorded version
    part = t.parts[0]
    others = [int(g) for g in cp.views[part.region].nodes
              if not cp._span_uses_node(t, int(g))]
    assert others, "need a node the placement does not touch"
    cp.fail_node(others[0])
    assert part.version < cp.views[part.region].version
    assert rid in cp.active_ids()  # untouched placement survived
    cp.check_invariants()


# ---------------------------------------------------------------------------
# nested views (hierarchical planes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_nested_bijection_round_trip_fuzz(seed):
    """A CompactedView of a CompactedView composes to the direct bijection:
    inner-local -> outer-local -> global round-trips exactly, and
    ``compose`` flattens the chain into the single equivalent view."""
    rng = np.random.default_rng(seed)
    rg = waxman(16 + 2 * seed, seed=seed)
    groups = partition_regions(rg, 2, seed=seed)
    outer = compact_view(rg, groups, int(rng.integers(0, 2)))
    # partition the outer view's compacted graph again (ids in [0, n_g))
    inner_assign = partition_regions(outer.graph(), 2, seed=seed + 1)
    for q in range(2):
        inner = outer.derive(np.nonzero(inner_assign == q)[0])
        assert inner._outer is outer and inner in outer._inner
        loc = np.arange(inner.n_local)
        # inner-local -> inner-base(=outer-local) -> global, vs composed
        direct = outer.compose(inner)
        np.testing.assert_array_equal(
            direct.nodes, outer.to_global(inner.to_global(loc)))
        np.testing.assert_array_equal(
            direct.to_global(loc), outer.to_global(inner.to_global(loc)))
        # round trip back down through both levels
        np.testing.assert_array_equal(
            inner.to_local(outer.to_local(direct.to_global(loc))), loc)
        # the composed compacted tensors equal slicing global directly
        g1 = direct.graph()
        g2 = inner.compact_graph(outer.graph())
        np.testing.assert_array_equal(g1.cap, g2.cap)
        np.testing.assert_array_equal(g1.bw, g2.bw)
        np.testing.assert_array_equal(g1.lat, g2.lat)
    # shape mismatches fail fast instead of mistranslating
    with pytest.raises(ValueError, match="cannot adopt"):
        outer.adopt(CompactedView.identity(rg))
    with pytest.raises(ValueError, match="cannot compose"):
        outer.compose(CompactedView.identity(rg))


def test_two_level_write_through_conservation():
    """Leaf placers nested two views deep (global -> group -> leaf) must
    re-assemble the global base exactly when lifted through the COMPOSED
    bijections — conservation survives nesting."""
    rg = waxman(18, seed=3)
    groups = partition_regions(rg, 2, seed=3)
    outers = [compact_view(rg, groups, g) for g in range(2)]
    leaves = []  # (composed view, leaf view, placer)
    for outer in outers:
        inner_assign = partition_regions(outer.graph(), 2, seed=5)
        for q in range(2):
            leaf = outer.derive(np.nonzero(inner_assign == q)[0])
            pl = OnlinePlacer(outer.graph(), view=leaf, **PYM)
            assert pl.base.n == leaf.n_local
            leaves.append((outer.compose(leaf), leaf, pl))
    rng = np.random.default_rng(7)
    for step in range(30):
        cv, leaf, pl = leaves[int(rng.integers(0, len(leaves)))]
        if rng.random() < 0.7 or not pl.tickets:
            if cv.n_local < 2:
                continue
            s, d = rng.choice(cv.n_local, size=2, replace=False)
            p = int(rng.integers(2, 4))
            pl.admit(DataflowPath(
                rng.uniform(0.02, 0.2, p).astype(np.float32),
                rng.uniform(0.5, 2.0, p - 1).astype(np.float32),
                int(s), int(d)))
        else:
            pl.release(next(iter(pl.tickets)))
        cap = np.zeros(rg.n)
        bw = np.zeros((rg.n, rg.n))
        in_region = np.zeros((rg.n, rg.n), bool)
        for cv2, _, pl2 in leaves:
            cap += cv2.uncompact_node_vec(pl2.cap)
            bw += cv2.uncompact_link_mat(pl2.bw)
            in_region |= cv2.uncompact_link_mat(
                np.ones((cv2.n_local, cv2.n_local), bool))
            for t in pl2.tickets.values():
                for gv, c in cv2.uncompact_node_load(t.node_load).items():
                    cap[gv] += c
                for (gu, gv), b in cv2.uncompact_edge_load(
                        t.edge_load).items():
                    bw[gu, gv] += b
        np.testing.assert_allclose(cap, rg.cap, atol=1e-4)
        np.testing.assert_allclose(bw[in_region], rg.bw[in_region], atol=1e-4)
    assert any(pl.stats.admitted for _, _, pl in leaves)


def test_invalidate_propagates_through_derivation_chain():
    """A leaf churn is visible at every enclosing level (ancestors bump);
    an outer invalidation cascades to every descendant; siblings are
    untouched — their slice of truth did not change."""
    rg = waxman(16, seed=6)
    groups = partition_regions(rg, 2, seed=6)
    outer0 = compact_view(rg, groups, 0)
    outer1 = compact_view(rg, groups, 1)
    a0 = partition_regions(outer0.graph(), 2, seed=0)
    leaf00 = outer0.derive(np.nonzero(a0 == 0)[0])
    leaf01 = outer0.derive(np.nonzero(a0 == 1)[0])
    a1 = partition_regions(outer1.graph(), 2, seed=0)
    leaf10 = outer1.derive(np.nonzero(a1 == 0)[0])

    # leaf churn: ancestors bump, siblings (and the other subtree) do not
    leaf00.invalidate()
    assert leaf00.version == 1 and outer0.version == 1
    assert leaf01.version == 0  # sibling untouched
    assert outer1.version == 0 and leaf10.version == 0  # other subtree
    # cached tensors of the invalidated chain were dropped and rebuild
    assert outer0.graph().n == outer0.n_local

    # outer churn: every descendant bumps, the other subtree does not
    outer0.invalidate()
    assert outer0.version == 2
    assert leaf00.version == 2 and leaf01.version == 1
    assert outer1.version == 0 and leaf10.version == 0

    # the regional plane drives this end to end: churn in one leaf region
    # of a hierarchy bumps the enclosing group view automatically
    from repro.core import region_tree

    trg, assign = region_tree(2, 2, 3, seed=1)
    cp = RegionalControlPlane(
        trg, levels=2, region_of=assign, seed=0, **PYM)
    cp.register_tenant("a")
    g = int(cp.region_of[0])
    top0 = cp.views[g].version
    leaf0 = cp.regions[g].views[0].version
    other = [v.version for v in cp.views if v is not cp.views[g]]
    cp.fail_node(0)
    assert cp.regions[g].views[0].version == leaf0 + 1
    assert cp.views[g].version == top0 + 1  # propagated up
    assert [v.version for v in cp.views if v is not cp.views[g]] == other
    cp.check_invariants()
