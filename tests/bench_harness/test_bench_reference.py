"""The plain reference against the program's solvers, and the trace
reduction on a small recorded trace."""
import math

import numpy as np
import pytest

from bench.harness import devtrace, network, reference


def _requests(net, count, seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        p = int(rng.integers(3, 6))
        creq = rng.uniform(0.3, 3.5, p).astype(np.float32)
        creq[0] = creq[-1] = 0.0
        breq = rng.uniform(4.0, 60.0, p - 1).astype(np.float32)
        src, dst = (int(x) for x in rng.choice(net.n, 2, replace=False))
        out.append((creq, breq, src, dst))
    return out


@pytest.mark.parametrize("seed", [0, 1])
def test_least_cost_matches_the_program(seed):
    from repro.core.graph import DataflowPath, ResourceGraph
    from repro.core.leastcost import leastcost_jax, leastcost_python

    net = network.region_tree(2, 3, 5, bw_range=(10.0, 40.0), seed=seed)
    rg = ResourceGraph(net.cap, net.bw, net.lat)
    ref = reference.Reference(net)
    for creq, breq, src, dst in _requests(net, 12, seed):
        df = DataflowPath(creq, breq, src, dst)
        want = [leastcost_python(rg, df)[0], leastcost_jax(rg, df)[0]]
        got = ref.least_cost(creq, breq, src, dst)
        for m in want:
            assert (m is None) == math.isinf(got)
            if m is not None:
                assert m.cost == pytest.approx(got, abs=1e-6)
                assert ref.invalid(creq, breq, src, dst, m.assign, m.route,
                                   m.cost) is None


def test_cut_relaxation_misses_long_routes():
    net = network.region_tree(3, 2, 4, seed=2)
    ref = reference.Reference(net)
    creq = np.array([0.0, 0.5, 0.0], np.float32)
    breq = np.array([5.0, 5.0], np.float32)
    far = net.n - 1
    full = ref.least_cost(creq, breq, 1, far)
    assert math.isfinite(full)
    assert math.isinf(ref.least_cost(creq, breq, 1, far, max_supersteps=2))


def test_ledger_and_validity_rules():
    net = network.region_tree(1, 2, 4, seed=0)
    ref = reference.Reference(net)
    creq = np.array([0.0, 1.0, 0.0], np.float32)
    breq = np.array([2.0, 3.0], np.float32)
    ok = ((0, 1, 1), (0, 1), 1.0)
    assert ref.invalid(creq, breq, 0, 1, *ok) is None
    assert "cost" in ref.invalid(creq, breq, 0, 1, (0, 1, 1), (0, 1), 2.0)
    assert ref.invalid(creq, breq, 0, 2, (0, 1, 2), (0, 1, 2), 2.0) is None
    assert "link" in ref.invalid(creq, breq, 0, 4, (0, 1, 4), (0, 1, 4), 2.0)
    assert ref.invalid(creq, breq, 0, 1, (0, 1, 1), (0, 2, 1), 2.0) is None
    ref.up[2] = False
    assert "failed" in ref.invalid(creq, breq, 0, 1, (0, 1, 1), (0, 2, 1),
                                   2.0)
    ref.up[2] = True
    big = np.array([0.0, 50.0, 0.0], np.float32)
    assert "capacity" in ref.invalid(big, breq, 0, 1, (0, 1, 1), (0, 1), 1.0)
    before = ref.cap.copy(), ref.bw.copy()
    ref.apply(creq, breq, (0, 1, 1), (0, 1), -1.0)
    assert ref.cap[1] == pytest.approx(before[0][1] - 1.0)
    assert ref.bw[ref.edge[(0, 1)]] == pytest.approx(
        before[1][ref.edge[(0, 1)]] - 2.0)
    ref.apply(creq, breq, (0, 1, 1), (0, 1), +1.0)
    assert np.allclose(ref.cap, before[0]) and np.allclose(ref.bw, before[1])


def _ev(name, start, dur, **stats):
    return devtrace.Event(name, start, dur, stats)


def test_trace_reduction_on_a_small_trace():
    planes = {
        "/host:CPU": {"python": [
            _ev("bench.window", 1000, 10000),
            _ev("bench.pump", 1000, 6000),
            _ev("bench.sleep", 7000, 4000),
        ]},
        "/device:TPU:0": {"XLA Modules": [
            _ev("jit__lambda(123)", 2000, 1000),
            _ev("jit__lambda(456)", 2500, 1000),
            _ev("jit_scatter-add(7)", 5000, 500),
            _ev("jit_scatter-add(7)", 10500, 1000),
        ], "XLA Ops": [_ev("%while.1 = (...)", 2000, 900)]},
        "/device:TPU:1": {"XLA Modules": [_ev("jit__lambda(123)", 1000, 2000)]},
    }
    red = devtrace.reduce(planes)
    assert red["chips"] == 2
    assert red["window_s"] == pytest.approx(10000e-9)
    # chip 0: [2000, 3500] + [5000, 5500] + [10500, 11000] = 2500 ns
    assert red["busy_s"] == pytest.approx((2500 + 2000) / 2 * 1e-9)
    assert red["device_ops"][0] == ["jit__lambda", pytest.approx(4000e-9)]
    assert red["device_ops"][1] == ["jit_scatter-add", pytest.approx(1000e-9)]
    gaps = red["idle_gaps"]
    assert gaps[0] == ["bench.sleep", pytest.approx(5000e-9)]
    assert ["bench.pump", pytest.approx(1500e-9)] in gaps
    assert ["bench.pump", pytest.approx(1000e-9)] in gaps


def test_trace_reduction_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(8)).block_until_ready()
    with devtrace.capture(str(tmp_path / "t")):
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("bench.pump"):
                f(jnp.ones(8)).block_until_ready()
    red = devtrace.reduce(devtrace.read(str(tmp_path / "t")))
    assert red["chips"] == 0 and red["busy_s"] == 0.0
    assert red["window_s"] > 0
    assert red["idle_gaps"][0][0] == "bench.pump"
