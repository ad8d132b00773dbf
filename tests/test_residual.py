"""The placer's float32 host view of the residual network: patched in place
by commits, releases and liveness changes, bit-identical to a full rebuild
after every mutation, and never changing under a graph already handed out."""
import gc

import numpy as np
import pytest

from repro.core import DataflowPath, ResourceGraph, random_dataflow, waxman
from repro.core import leastcost
from repro.core.problem import finite_lat
from repro.core.residual import ResidualState
from repro.obs import MetricsRegistry, absorb_residual_stats
from repro.service import ControlPlane

JM = dict(method="leastcost_jax")  # the batched DP: dispatch/finalize path


def _assert_is_full_view(res: ResidualState, rg: ResourceGraph) -> None:
    full = res._full_view()
    for name in ("cap", "bw", "lat"):
        got = getattr(rg, name)
        assert got.dtype == np.float32, name
        assert np.array_equal(got, full[name]), name


def _random_loads(rng, res: ResidualState, edges) -> tuple[dict, dict]:
    nodes = rng.choice(res.base.n, size=int(rng.integers(1, 6)), replace=False)
    node_load = {int(v): float(rng.uniform(0.0, 0.3)) for v in nodes}
    picked = rng.choice(len(edges), size=int(rng.integers(0, 5)), replace=False)
    edge_load = {edges[i]: float(rng.uniform(0.0, 2.0)) for i in picked}
    return node_load, edge_load


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_view_equals_a_full_rebuild_after_every_mutation(seed):
    rng = np.random.default_rng(seed)
    base = waxman(48, seed=seed)
    res = ResidualState(base)
    edges = list(base.edges())
    snaps, down_nodes, down_links = [], [], []
    for _ in range(120):
        op = rng.choice(
            ["commit", "release", "node_down", "node_up", "link_down",
             "link_up", "snapshot", "restore", "sync"],
            p=[0.3, 0.2, 0.08, 0.08, 0.08, 0.08, 0.06, 0.06, 0.06])
        if op in ("commit", "release"):
            res.apply_load(*_random_loads(rng, res, edges),
                           -1.0 if op == "commit" else 1.0)
        elif op == "node_down":
            v = int(rng.integers(0, base.n))
            res.set_node_up(v, False)
            down_nodes.append(v)
        elif op == "node_up" and down_nodes:
            res.set_node_up(down_nodes.pop(0), True)
        elif op == "link_down":
            u, v = edges[int(rng.integers(0, len(edges)))]
            res.set_link_up(u, v, False)
            down_links.append((u, v))
        elif op == "link_up" and down_links:
            res.set_link_up(*down_links.pop(0), True)
        elif op == "snapshot":
            snaps.append(res.snapshot())
        elif op == "restore" and snaps:
            res.restore(snaps[int(rng.integers(0, len(snaps)))])
        elif op == "sync":
            res.device_tensors()
        # the live view after each step, and every few steps a frozen one
        # (which makes the next mutation copy on write)
        _assert_is_full_view(res, res.residual_graph(frozen=False))
        if rng.random() < 0.3:
            _assert_is_full_view(res, res.residual_graph())
    stats = res.sync_stats
    assert stats["patched_entries"] > 0 and stats["cow_copies"] > 0


def test_handed_out_graph_never_changes_and_back_to_back_commits_copy_nothing():
    base = waxman(16, seed=2)
    res = ResidualState(base)
    (u, v), (x, y) = list(base.edges())[:2]
    load = ({u: 0.25, v: 0.5}, {(u, v): 1.5})
    held = res.residual_graph()
    before = {k: getattr(held, k).copy() for k in ("cap", "bw", "lat")}
    res.apply_load(*load, -1.0)  # commit
    res.apply_load({x: 0.1}, {(x, y): 0.75}, -1.0)
    res.apply_load(*load, 1.0)  # release
    res.set_node_up(u, False)  # node failure
    res.set_link_up(x, y, False)
    for k, arr in before.items():
        assert np.array_equal(getattr(held, k), arr), k
    _assert_is_full_view(res, res.residual_graph(frozen=False))
    # no handout since the last copy: commits patch the view in place
    copies = res.sync_stats["cow_copies"]
    res.apply_load(*load, -1.0)
    res.apply_load({x: 0.1}, {(x, y): 0.75}, -1.0)
    assert res.sync_stats["cow_copies"] == copies
    # nor does a live read pin anything
    res.residual_graph(frozen=False)
    res.apply_load(*load, 1.0)
    assert res.sync_stats["cow_copies"] == copies


@pytest.mark.parametrize("holder", ["handout", "snapshot"])
def test_a_dropped_holder_lets_commits_write_the_view_in_place(holder):
    base = waxman(16, seed=5)
    res = ResidualState(base)
    (u, v), (x, y) = list(base.edges())[:2]
    load = ({u: 0.25, v: 0.5}, {(u, v): 1.5, (x, y): 0.75})
    held = res.residual_graph() if holder == "handout" else res.snapshot()
    del held
    gc.collect()
    copies = res.sync_stats["cow_copies"]
    res.apply_load(*load, -1.0)  # commit
    _assert_is_full_view(res, res.residual_graph(frozen=False))
    res.apply_load(*load, 1.0)  # release
    assert res.sync_stats["cow_copies"] == copies
    assert res.sync_stats["cow_avoided"] == 1  # one copy saved, not two
    _assert_is_full_view(res, res.residual_graph(frozen=False))


def _assert_mirror_is_truth(res: ResidualState) -> None:
    """Device tensors against float32 of the float64 truth with liveness
    applied, within the drift of incremental float32 scatter-adds."""
    dev = res.device_tensors()
    full = res._full_view()
    np.testing.assert_allclose(np.asarray(dev["cap"]), full["cap"],
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dev["bw"]), full["bw"],
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(dev["lat"]),
                                  finite_lat(ResourceGraph(**full)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_full_upload_is_not_changed_by_later_writes(seed):
    """On the CPU ``jnp.asarray`` may alias a host array zero-copy: a view
    written in place after a full upload would change the device tensor
    and count every later delta twice.  Several upload cycles, each of a
    freshly allocated view, so that some upload is aliased."""
    rng = np.random.default_rng(seed)
    base = waxman(256, seed=seed)
    res = ResidualState(base)
    edges = list(base.edges())
    for cycle in range(8):
        res.device_tensors()  # full upload
        for _ in range(3):
            load = _random_loads(rng, res, edges)
            res.apply_load(*load, -1.0)
            _assert_mirror_is_truth(res)
            res.apply_load(*load, 1.0)
        res.apply_load(*_random_loads(rng, res, edges), -1.0)
        _assert_mirror_is_truth(res)
        held = res.residual_graph()  # so the liveness change copies the view
        v = int(rng.integers(0, base.n))
        res.set_node_up(v, False)  # drops the mirror
        res.set_node_up(v, True)
        del held
    assert res.sync_stats["full_uploads"] == 8


@pytest.mark.parametrize("others", ["held", "dropped"])
def test_a_held_snapshot_restores_bit_identically(others):
    rng = np.random.default_rng(11)
    base = waxman(48, seed=11)
    res = ResidualState(base)
    edges = list(base.edges())
    res.device_tensors()
    res.apply_load(*_random_loads(rng, res, edges), -1.0)
    snap = res.snapshot()
    want = {k: v.copy() for k, v in res._full_view().items()}
    truth = (res.cap.copy(), res.bw.copy())
    extra = []
    for step in range(3):
        for _ in range(5):
            res.apply_load(*_random_loads(rng, res, edges), -1.0)
        extra.append(res.snapshot())
        res.restore(snap)
        _assert_is_full_view(res, res.residual_graph(frozen=False))
        assert np.array_equal(res.cap, truth[0])
        assert np.array_equal(res.bw, truth[1])
        for k, arr in want.items():
            assert np.array_equal(snap["view"][k], arr), (step, k)
            assert np.array_equal(res._view[k], arr), (step, k)
        _assert_mirror_is_truth(res)
        if others == "dropped":
            extra.clear()
            gc.collect()


@pytest.mark.parametrize("depth", [1, 2])
def test_dispatched_graph_is_unchanged_until_reconstructed(depth, monkeypatch):
    """Whatever the pipeline commits between a batch's dispatch and its
    reconstruction, the batch walks the graph it was solved on."""
    at_dispatch = {}
    dispatch = leastcost.leastcost_jax_batched_dispatch
    finalize = leastcost.leastcost_jax_batched_finalize

    def recording_dispatch(rg, dfs, **kw):
        pending = dispatch(rg, dfs, **kw)
        at_dispatch[id(pending)] = {
            k: getattr(rg, k).copy() for k in ("cap", "bw", "lat")}
        return pending

    def checking_finalize(pending, **kw):
        for k, arr in at_dispatch.pop(id(pending)).items():
            assert np.array_equal(getattr(pending.rg, k), arr), k
        return finalize(pending, **kw)

    monkeypatch.setattr(leastcost, "leastcost_jax_batched_dispatch",
                        recording_dispatch)
    monkeypatch.setattr(leastcost, "leastcost_jax_batched_finalize",
                        checking_finalize)
    rg, dfs = _light()
    cp = ControlPlane(rg, micro_batch=2, pipeline_depth=depth, **JM)
    cp.register_tenant("a")
    for df in dfs:
        cp.submit("a", df)
    cp.pump(rounds=len(dfs))
    cp.flush()
    cp.check_invariants()
    assert not at_dispatch
    assert len(cp.placer.tickets) == len(dfs)


def _contended() -> tuple[ResourceGraph, list[DataflowPath]]:
    """0 -> 2 via node 1 (latency 2) or node 3 (latency 4), capacity 1 on
    each: the first request fills node 1, so the second, dispatched before
    that commit, conflicts and goes via node 3."""
    rg = ResourceGraph.from_edge_list(
        [0.0, 1.0, 0.0, 1.0],
        [(0, 1, 10.0, 1.0), (1, 2, 10.0, 1.0), (0, 3, 10.0, 2.0),
         (3, 2, 10.0, 2.0)])
    dfs = [DataflowPath.make([0.0, c, 0.0], [1.0, 1.0], src=0, dst=2)
           for c in (1.0, 0.9)]
    return rg, dfs


def _light() -> tuple[ResourceGraph, list[DataflowPath]]:
    rg = waxman(24, seed=7)
    return rg, [random_dataflow(rg, 4, seed=700 + i, creq_range=(0.05, 0.2),
                                breq_range=(0.5, 2.0)) for i in range(12)]


@pytest.mark.parametrize("network", [_contended, _light])
def test_pipelined_batches_reconstruct_on_their_dispatch_graph(network):
    rg, dfs = network()
    out = []
    for depth in (1, 2):
        cp = ControlPlane(rg, micro_batch=1 if len(dfs) == 2 else 4,
                          pipeline_depth=depth, **JM)
        cp.register_tenant("a")
        for df in dfs:
            cp.submit("a", df)
        cp.pump(rounds=len(dfs))
        cp.flush()
        cp.check_invariants()
        assert cp.placer.stats.fallbacks == 0
        out.append(sorted((t.tid, t.mapping.route, t.mapping.cost)
                          for t in cp.placer.tickets.values()))
    assert out[0] == out[1]
    assert len(out[0]) == len(dfs)


def test_cold_pump_patches_the_view_and_registers_its_counters():
    rg = waxman(16, seed=3)
    cp = ControlPlane(rg, micro_batch=8, **JM)
    cp.register_tenant("a")
    for i in range(6):
        cp.submit("a", random_dataflow(rg, 3, seed=40 + i,
                                       creq_range=(0.05, 0.2),
                                       breq_range=(0.5, 2.0)))
    stats = cp.placer.res.sync_stats
    full_views = stats["full_views"]
    assert cp.pump(rounds=1)
    assert stats["full_views"] == full_views
    assert stats["patched_entries"] > 0
    # depth 1: the classify and dispatched graphs are dropped before the
    # commits, so the one copy is for the mirror's live upload handout
    assert stats["full_uploads"] == 1
    assert (stats["cow_copies"], stats["cow_avoided"]) == (1, 0)
    for i in range(3):
        cp.submit("a", random_dataflow(rg, 3, seed=60 + i,
                                       creq_range=(0.05, 0.2),
                                       breq_range=(0.5, 2.0)))
    assert cp.pump(rounds=1)  # a second pump copies nothing
    assert (stats["cow_copies"], stats["cow_avoided"]) == (1, 1)
    reg = absorb_residual_stats(MetricsRegistry(), cp.placer.res)
    assert reg.get("residual.patched_entries") == stats["patched_entries"]
    assert reg.get("residual.cow_copies") == stats["cow_copies"]
    assert reg.get("residual.cow_avoided") == stats["cow_avoided"]
    assert reg.get("residual.full_views") == full_views
    assert cp.metrics_registry().get("residual.patched_entries") == (
        stats["patched_entries"])
    assert cp.metrics_registry().get("residual.cow_avoided") == 1
