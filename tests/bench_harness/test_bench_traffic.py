"""Traffic generation, percentile arithmetic and discovery by name."""
import json
import math
import os
import shutil

import numpy as np
import pytest

from bench.harness import catalog, network, stats, traffic

ROOT = catalog.ROOT


def _mix(process="poisson"):
    mix = catalog.traffic("steady_flat4k")
    if process != "poisson":
        mix = catalog.traffic("overload_flat4k")
    return mix


@pytest.fixture(scope="module")
def net():
    return network.region_tree(2, 2, 8, seed=3)


@pytest.mark.parametrize("process", ["poisson", "pareto_bursts"])
def test_generator_is_deterministic_for_a_seed(net, process):
    a = traffic.build(_mix(process), net, seconds=20.0, seed=2**31 + 5,
                      standing=4)
    b = traffic.build(_mix(process), net, seconds=20.0, seed=2**31 + 5,
                      standing=4)
    assert len(a.arrivals) == len(b.arrivals) > 0
    for x, y in zip(a.arrivals + a.standing, b.arrivals + b.standing):
        assert (x.due, x.src, x.dst, x.klass, x.hold) == (
            y.due, y.src, y.dst, y.klass, y.hold)
        assert np.array_equal(x.creq, y.creq)
        assert np.array_equal(x.breq, y.breq)
    assert (a.fail_at, a.fail_nodes) == (b.fail_at, b.fail_nodes)


@pytest.mark.parametrize("process", ["poisson", "pareto_bursts"])
def test_seeds_reorder_the_same_work(net, process):
    a = traffic.build(_mix(process), net, seconds=20.0, seed=1)
    b = traffic.build(_mix(process), net, seconds=20.0, seed=2)
    assert [r.due for r in a.arrivals] == [r.due for r in b.arrivals]
    key = lambda r: (r.p, float(r.creq.sum()), float(r.breq.sum()))  # noqa
    assert list(map(key, a.arrivals)) != list(map(key, b.arrivals))
    assert sorted(map(key, a.arrivals)) == sorted(map(key, b.arrivals))
    assert all(0 <= r.due < 20.0 for r in a.arrivals)
    assert [r.due for r in a.arrivals] == sorted(r.due for r in a.arrivals)


def test_requests_follow_the_mix(net):
    s = traffic.build(_mix(), net, seconds=200.0, seed=9)
    for r in s.arrivals:
        assert 3 <= r.p <= 5 and r.creq[0] == r.creq[-1] == 0
        assert np.all((r.breq >= 4.0) & (r.breq <= 18.0))
        assert r.src != r.dst
    local = np.mean([net.leaf_of[r.src] == net.leaf_of[r.dst]
                     for r in s.arrivals])
    assert 0.7 < local < 0.95
    assert len(s.fail_nodes) == 8  # 16 asked, capped at one leaf
    assert len({int(net.leaf_of[v]) for v in s.fail_nodes}) == 1


def test_undecided_requests_enter_at_their_age():
    due = [0.0, 1.0, 2.0, 9.0]
    # 12.0 is decided in the drain after a 10 s window, which stopped at 15.0
    decided = [0.5, None, 12.0, 9.25]
    lat = stats.latencies_ms(due, decided, 15.0)
    assert lat.tolist() == [500.0, 14000.0, 10000.0, 250.0]
    assert stats.percentile(lat, 50) == pytest.approx(5250.0)
    assert stats.rate(3, 10.0) == 0.3


def test_discovery_by_file_name(tmp_path):
    root = tmp_path
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    bench = {"configs": [{"name": "cfg-x", "file": "bench/configs/cfg-x.json"}],
             "workloads": [{"name": "cell-x", "config": "cfg-x",
                            "traffic": "mix-x", "chips": 1}],
             "end_to_end": [{"name": "setup_s", "unit": "s"},
                            {"name": "decisions_per_s", "unit": "decisions/s",
                             "workloads": ["other"]}],
             "per_layer": [{"name": "metric-x", "moves": "setup_s",
                            "unit": "ms"}]}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "configs" / "cfg-x.json").write_text('{"k": 1}')
    (root / "bench" / "traffic" / "mix-x.json").write_text('{"m": 2}')
    (root / "bench" / "metrics" / "metric-x.py").write_text(
        "def read(ctx):\n    return ctx['v'] * 2\n")
    b = catalog.benchmark(str(root))
    cell = catalog.cell(b, "cell-x")
    assert catalog.config(b, cell["config"], str(root)) == {"k": 1}
    assert catalog.traffic(cell["traffic"], str(root / "bench")) == {"m": 2}
    assert catalog.metric_reader("metric-x", str(root / "bench"))(
        {"v": 3}) == 6
    assert [m["name"] for m in catalog.metrics_of(b, "cell-x",
                                                  "end_to_end")] == ["setup_s"]
    assert [m["name"] for m in catalog.metrics_of(b, "cell-x",
                                                  "per_layer")] == ["metric-x"]


def test_every_name_in_the_benchmark_has_its_file():
    b = catalog.benchmark()
    for w in b["workloads"]:
        catalog.config(b, w["config"])
        catalog.traffic(w["traffic"])
        for kind in ("end_to_end", "per_layer"):
            assert catalog.metrics_of(b, w["name"], kind)
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(catalog.metric_reader(m["name"]))
    names = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        e2e = {m["name"] for m in catalog.metrics_of(b, w["name"],
                                                     "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        for m in catalog.metrics_of(b, w["name"], "per_layer"):
            assert m["moves"] in e2e and m["moves"] in names


def test_network_matches_the_bring_up_topology():
    from repro.core.topology import region_tree

    rg, assign = region_tree(2, 3, 5, seed=11)
    net = network.region_tree(2, 3, 5, seed=11)
    assert np.array_equal(rg.cap, net.cap) and np.array_equal(rg.bw, net.bw)
    assert np.array_equal(rg.lat, net.lat)
    assert np.array_equal(assign, net.leaf_of)
    assert not math.isfinite(float(net.lat[0, net.n - 1]))


def test_backlog_is_due_at_the_start(net):
    mix = _mix("pareto_bursts")
    plain = dict(mix, arrivals=dict(mix["arrivals"], backlog=0))
    a = traffic.build(mix, net, seconds=20.0, seed=4)
    b = traffic.build(plain, net, seconds=20.0, seed=4)
    n = int(mix["arrivals"]["backlog"])
    assert n > 0 and len(a.arrivals) == len(b.arrivals) + n
    assert [r.due for r in a.arrivals[:n]] == [0.0] * n
    assert [r.due for r in a.arrivals[n:]] == [r.due for r in b.arrivals]


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_failure_displaces_the_same_requests_on_every_seed(net, seed):
    mix = _mix()
    s = traffic.build(mix, net, seconds=20.0, seed=seed, standing=4)
    leaf = int(net.leaf_of[s.fail_nodes[0]])
    assert leaf % mix["block_leaves"] != 0
    pinned = mix["churn"]["pinned"]
    assert pinned > 0
    for r in s.arrivals[:pinned]:
        assert r.src in s.fail_nodes and r.due < s.fail_at
    for r in s.arrivals[pinned:] + s.standing:
        assert leaf not in (net.leaf_of[r.src], net.leaf_of[r.dst])
    first = traffic.build(mix, net, seconds=20.0, seed=seed + 1)
    for a, b in zip(s.arrivals[:pinned], first.arrivals[:pinned]):
        assert (a.due, a.p, a.hold) == (b.due, b.p, b.hold)
        assert np.array_equal(a.creq, b.creq)
