"""Unified telemetry plane: metrics registry semantics (labels, merge
composition, windowed snapshots), request-lifecycle tracer (spans, flow
events, scoped prefixes, null-tracer zero-cost guarantees), Chrome-trace
export + lifecycle reconstruction, the non-additive engine-stats fold fix
(kernel_impl / solve_n at every plane level), the solve/overhead/conflict
timing split across sync and pipelined admission, and bit-for-bit identity
of traced vs untraced planes."""
import json

import numpy as np
import pytest

from repro.core import (
    DataflowPath,
    random_dataflow,
    region_line,
    region_tree,
    waxman,
)
from repro.core.engine import Stats
from repro.core.online import OnlinePlacer
from repro.obs import (
    NULL,
    Histogram,
    MetricsRegistry,
    NullTracer,
    Tracer,
    absorb_engine_stats,
    absorb_gossip_stats,
    absorb_online_stats,
    reconstruct_request,
    text_timeline,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.service import (
    ControlPlane,
    FairSharePolicy,
    GossipBus,
    RegionalControlPlane,
)

PYM = dict(method="leastcost_python")  # pure-python backend: fast, no jit


def _unit_df(creq: float = 1.0, src: int = 0, dst: int = 2) -> DataflowPath:
    return DataflowPath.make([0.0, creq, 0.0], [1.0, 1.0], src, dst)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.inc("admit.total")
    reg.inc("admit.total", 2.0)
    reg.gauge("queue.depth", 7.0)
    reg.observe("solve.ms", 3.0)
    reg.observe("solve.ms", 5.0)
    assert reg.get("admit.total") == 3.0
    assert reg.get("queue.depth") == 7.0
    # get() on a histogram series reads its mean; labeled() exposes the
    # full summary
    assert reg.get("solve.ms") == pytest.approx(4.0)
    h = reg.labeled("solve.ms")[()]
    assert h["count"] == 2 and h["sum"] == pytest.approx(8.0)
    assert h["min"] == 3.0 and h["max"] == 5.0


def test_registry_labels_total_and_labeled():
    reg = MetricsRegistry()
    reg.inc("solves", 3.0, kernel_impl="pallas")
    reg.inc("solves", 1.0, kernel_impl="ref")
    reg.inc("solves", 2.0, kernel_impl="pallas")
    assert reg.total("solves") == 6.0
    by = reg.labeled("solves")
    assert by[(("kernel_impl", "pallas"),)] == 5.0
    assert by[(("kernel_impl", "ref"),)] == 1.0
    # unlabeled get with labels selects the exact series
    assert reg.get("solves", kernel_impl="ref") == 1.0


def test_registry_merge_composes_label_paths():
    """Merging child registries tags series with the child's position;
    nesting composes paths the way plane nesting does (g0/r1)."""
    leaf = MetricsRegistry()
    leaf.inc("admitted", 4.0)
    mid = MetricsRegistry()
    mid.merge(leaf, plane="r1")
    assert mid.get("admitted", plane="r1") == 4.0
    top = MetricsRegistry()
    top.merge(mid, plane="g0")
    # duplicate label key composes into a path, outermost first
    assert top.get("admitted", plane="g0/r1") == 4.0
    assert top.total("admitted") == 4.0


def test_registry_merge_sums_same_series_and_histograms():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("c", 1.0)
    b.inc("c", 2.0)
    a.observe("h", 1.0)
    b.observe("h", 3.0)
    a.merge(b)
    assert a.get("c") == 3.0
    h = a.labeled("h")[()]
    assert h["count"] == 2 and h["sum"] == pytest.approx(4.0)
    assert h["min"] == 1.0 and h["max"] == 3.0


def test_registry_snapshot_flat_and_reset():
    reg = MetricsRegistry()
    reg.inc("a", 2.0)
    reg.gauge("g", 1.5)
    reg.observe("h", 4.0)
    snap = reg.snapshot()
    assert snap["a"] == 2.0 and snap["g"] == 1.5
    assert snap["h"]["count"] == 1
    # snapshot must be JSON-serializable (bench records embed it)
    json.dumps(snap)
    reg.snapshot(reset=True)
    assert reg.snapshot() == {}


def test_histogram_pow2_buckets_and_merge():
    h = Histogram()
    for v in (0.5, 1.0, 2.0, 3.0, 700.0):
        h.observe(v)
    d = h.to_dict()
    assert d["count"] == 5 and d["max"] == 700.0
    g = Histogram()
    g.observe(10.0)
    h.merge(g)
    assert h.count == 6
    assert sum(h.buckets.values()) == 6


def test_absorb_adapters_smoke():
    reg = MetricsRegistry()
    s = Stats(method="leastcost_python", rounds=3, solve_n=12,
              kernel_impl="ref", max_set_size=9, gossip_messages=7)
    absorb_engine_stats(reg, s)
    assert reg.total("engine.rounds") == 3.0
    assert reg.total("engine.gossip_messages") == 7.0
    assert reg.get("engine.max_set_size") == 9.0
    assert reg.get("engine.solves", kernel_impl="ref") == 1.0
    absorb_gossip_stats(reg, {"rounds": 2, "messages_sent": 6,
                              "records_sent": 12, "payload_sent": 48})
    assert reg.total("gossip.messages_sent") == 6.0


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_records_spans_instants_and_flows():
    tr = Tracer()
    with tr.span("solve", track="placer", cat="solve", n=8):
        pass
    tr.instant("epoch", track="placer")
    tr.flow_begin(5, "submit", tenant="a")
    tr.flow_point(5, "dispatch")
    tr.flow_end(5, "release", outcome="released")
    evs = tr.events
    phs = [e["ph"] for e in evs]
    assert phs == ["X", "i", "b", "n", "e"]
    x = evs[0]
    assert x["name"] == "solve" and x["dur"] >= 0 and x["args"]["n"] == 8
    for e in evs[2:]:
        assert e["id"] == "req:5" and e["cat"]
    tr.clear()
    assert tr.events == []


def test_tracer_scoped_prefixes_share_one_buffer():
    tr = Tracer()
    r0 = tr.scoped("r0")
    g = tr.scoped("g1").scoped("r2")
    tr.flow_begin(1, "submit")
    r0.flow_point(1, "dispatch")
    g.flow_point(1, "2pc.reserve")
    ids = [e["id"] for e in tr.events]
    assert ids == ["req:1", "r0/req:1", "g1/r2/req:1"]
    # scoped views write into the parent's buffer, not their own
    assert r0.events is tr.events or list(r0.events) == list(tr.events)


def test_null_tracer_is_inert():
    assert isinstance(NULL, NullTracer) and not NULL.enabled
    # span returns a shared no-op context: no per-call allocation
    assert NULL.span("x") is NULL.span("y", track="t", cat="c", k=1)
    with NULL.span("x"):
        pass
    NULL.instant("i")
    NULL.flow_begin(1, "submit")
    NULL.flow_point(1, "p")
    NULL.flow_end(1, "e")
    assert NULL.events == []
    assert NULL.scoped("r0") is NULL


# ---------------------------------------------------------------------------
# chrome-trace export
# ---------------------------------------------------------------------------


def test_chrome_export_schema_and_timeline(tmp_path):
    tr = Tracer()
    with tr.span("pump.round", track="pump", cat="pump"):
        with tr.span("solve", track="placer", cat="solve"):
            pass
    tr.flow_begin(0, "submit")
    tr.flow_end(0, "release")
    doc = to_chrome_trace(tr)
    assert validate_chrome_trace(doc) == []
    # metadata names every track; real events carry pid/tid
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(m["name"] == "process_name" for m in metas)
    path = tmp_path / "trace.json"
    out = write_chrome_trace(tr, str(path))
    assert validate_chrome_trace(json.loads(path.read_text())) == []
    assert out["traceEvents"]
    txt = text_timeline(tr)
    assert "pump.round" in txt


def test_validate_rejects_malformed_traces():
    assert validate_chrome_trace({"nope": 1})
    bad = {"traceEvents": [{"ph": "X", "name": "a", "ts": 0.0,
                            "pid": 1, "tid": 1}]}  # X without dur
    assert validate_chrome_trace(bad)
    unbalanced = {"traceEvents": [
        {"ph": "b", "name": "s", "cat": "lc", "id": "req:1",
         "ts": 0.0, "pid": 1, "tid": 1}]}
    assert validate_chrome_trace(unbalanced)


def _line_rg(mid_cap: float = 4.0):
    # 0 -- 1 -- 2 line; only node 1 has capacity
    rg = waxman(3, seed=0)
    rg.cap[:] = [0.0, mid_cap, 0.0]
    return rg


def test_centralized_lifecycle_reconstructable():
    tr = Tracer()
    rg = waxman(8, seed=4)
    cp = ControlPlane(rg, micro_batch=4, tracer=tr, **PYM)
    cp.register_tenant("a", weight=1.0)
    rid = cp.submit("a", random_dataflow(rg, 3, seed=1,
                                         creq_range=(0.05, 0.2),
                                         breq_range=(0.5, 2.0)))
    admitted = cp.pump(rounds=2)
    assert admitted, "scenario must admit for the lifecycle to exist"
    cp.release(rid)
    doc = to_chrome_trace(tr)
    assert validate_chrome_trace(doc) == []
    life = reconstruct_request(doc, rid)
    names = [e["name"] for e in life]
    assert names[0] == "submit" and names[-1] == "release"
    assert "admit" in names
    ts = [e["ts"] for e in life]
    assert ts == sorted(ts)


def test_spanning_lifecycle_reconstructable_across_regions():
    """Acceptance shape: one spanning request's submit -> chained 2PC
    reserves across >= 2 regions -> commit -> release is recoverable from
    the exported trace by rid alone."""
    R, k = 3, 4
    rg, assign = region_line(R, k, seed=9)
    tr = Tracer()
    cp = ControlPlane(rg, region_of=assign, micro_batch=8, fanout=2,
                      seed=9, tracer=tr, **PYM)
    cp.register_tenant("a", weight=1.0)
    df = DataflowPath.make([0.0, 0.1, 0.0], [0.5, 0.5], 0, rg.n - 1)
    rid = cp.submit("a", df, klass=1)
    for _ in range(6):
        cp.pump()
        if rid in cp.active_ids():
            break
    assert rid in cp.active_ids()
    cp.release(rid)
    doc = to_chrome_trace(tr)
    assert validate_chrome_trace(doc) == []
    life = reconstruct_request(doc, rid)
    names = [e["name"] for e in life]
    assert names[0] == "submit" and names[-1] == "release"
    assert names.count("2pc.reserve") >= 2
    assert "2pc.commit" in names and "admit" in names
    regions = {e["args"]["region"] for e in life
               if e["name"] == "2pc.reserve" and "region" in e.get("args", {})}
    assert len(regions) >= 2


def test_bit_identity_with_tracing_enabled():
    """A live Tracer must not perturb placement: traced and untraced
    planes replay the same fuzzed op sequence bit for bit."""
    rg = waxman(12, seed=5)
    kw = dict(micro_batch=6, max_attempts=3,
              policy=FairSharePolicy(slack=0.4), **PYM)
    a = ControlPlane(rg, **kw)
    b = ControlPlane(rg, tracer=Tracer(), **kw)
    for cp in (a, b):
        cp.register_tenant("x", weight=2.0)
        cp.register_tenant("y", weight=1.0)
    rng = np.random.default_rng(7)
    for step in range(30):
        op = rng.choice(["submit", "pump", "release"], p=[0.5, 0.35, 0.15])
        if op == "submit":
            df = random_dataflow(rg, 4, seed=900 + step,
                                 creq_range=(0.05, 0.3),
                                 breq_range=(0.5, 3.0))
            t = str(rng.choice(["x", "y"]))
            assert a.submit(t, df) == b.submit(t, df)
        elif op == "pump":
            assert ([t.tid for t in a.pump()]
                    == [t.tid for t in b.pump()])
        elif op == "release":
            ids = a.active_ids()
            assert ids == b.active_ids()
            if ids:
                rid = int(rng.choice(ids))
                a.release(rid)
                b.release(rid)
        np.testing.assert_array_equal(a.placer.cap, b.placer.cap)
        np.testing.assert_array_equal(a.placer.bw, b.placer.bw)
    assert len(b.tracer.events) > 0


# ---------------------------------------------------------------------------
# timing split (solve / overhead / conflict) — satellite 3
# ---------------------------------------------------------------------------


def _pumped_plane(**kw):
    rg = waxman(10, seed=3)
    cp = ControlPlane(rg, micro_batch=4, **kw, **PYM)
    cp.register_tenant("a", weight=1.0)
    for i in range(6):
        cp.submit("a", random_dataflow(rg, 3, seed=40 + i,
                                       creq_range=(0.05, 0.2),
                                       breq_range=(0.5, 2.0)))
    cp.pump(rounds=3)
    return cp


def test_timing_split_present_and_nonnegative_centralized():
    cp = _pumped_plane()
    t = cp.fairness_report()["timing"]
    assert set(t) == {"solve_ms", "overhead_ms", "conflict_resolve_ms"}
    assert all(v >= 0.0 for v in t.values())
    assert t["solve_ms"] > 0.0  # solves happened


def _regional_timing(levels=None):
    rg, assign = region_line(2, 4, seed=2)
    kw = dict(region_of=assign, micro_batch=4, seed=2, **PYM)
    if levels is not None:
        kw["levels"] = levels
    cp = ControlPlane(rg, **kw)
    cp.register_tenant("a", weight=1.0)
    for i in range(4):
        cp.submit("a", random_dataflow(rg, 3, seed=60 + i,
                                       creq_range=(0.05, 0.2),
                                       breq_range=(0.5, 2.0)))
    cp.pump(rounds=3)
    return cp.fairness_report()["timing"]


def test_timing_split_present_regional_plane():
    t = _regional_timing()
    assert set(t) == {"solve_ms", "overhead_ms", "conflict_resolve_ms"}
    assert all(v >= 0.0 for v in t.values())
    assert t["solve_ms"] > 0.0


def test_timing_split_present_hierarchical_plane():
    rg, assign = region_tree(2, 2, 3, seed=1)
    cp = ControlPlane(rg, region_of=assign, levels=2, branching=2,
                      micro_batch=4, seed=1, **PYM)
    cp.register_tenant("a", weight=1.0)
    for i in range(4):
        cp.submit("a", random_dataflow(rg, 3, seed=80 + i,
                                       creq_range=(0.05, 0.2),
                                       breq_range=(0.5, 2.0)))
    cp.pump(rounds=3)
    t = cp.fairness_report()["timing"]
    assert set(t) == {"solve_ms", "overhead_ms", "conflict_resolve_ms"}
    assert all(v >= 0.0 for v in t.values())
    assert t["solve_ms"] > 0.0


def test_timing_split_accumulates_on_pipelined_path():
    """dispatch_admit/commit_admit must feed the same timing counters as
    the synchronous admit_many path — and produce the same tickets."""
    rg = waxman(10, seed=6)
    dfs = [random_dataflow(rg, 3, seed=500 + i, creq_range=(0.05, 0.2),
                           breq_range=(0.5, 2.0)) for i in range(4)]
    sync = OnlinePlacer(rg, **PYM)
    t_sync = sync.admit_many(list(dfs))
    pipe = OnlinePlacer(rg, **PYM)
    pending = pipe.dispatch_admit(list(dfs))
    t_pipe = pipe.commit_admit(pending)
    assert ([t.tid for t in t_sync if t]
            == [t.tid for t in t_pipe if t])
    for st in (sync.stats, pipe.stats):
        assert st.solve_ms > 0.0
        assert st.overhead_ms >= 0.0
        assert st.conflict_resolve_ms >= 0.0
        assert st.solves > 0 and st.solve_n_sum > 0


def test_timing_and_kernel_impls_survive_defrag_and_preempt():
    cp = _pumped_plane(preempt=True)
    st = cp.placer.stats
    # the pure-python backend records no kernel impl; seed the labeled
    # counts the way a kernel backend would to exercise the stats surgery
    st.kernel_impls["ref"] = 3
    solve_before = st.solve_ms
    assert solve_before > 0.0
    cp.defrag()
    st = cp.placer.stats
    # snapshot/rollback around defrag must not lose the non-additive
    # carries or rewind the timing accumulators
    assert st.kernel_impls.get("ref", 0) >= 3
    assert st.solve_ms >= solve_before
    assert st.defrag_rounds >= 1


# ---------------------------------------------------------------------------
# kernel_impl / solve_n fold fix — satellite 1
# ---------------------------------------------------------------------------


def test_engine_stats_carries_kernel_impl_and_solve_n_centralized():
    cp = _pumped_plane()
    # the python backend reports no kernel impl, so seed the labeled count
    # a kernel backend would have left; the fold used to drop it entirely
    cp.placer.stats.kernel_impls["ref"] = cp.placer.stats.solves
    s = cp.engine_stats()
    assert s.kernel_impl == "ref"
    assert s.solve_n > 0  # mean padded solve dimension, not the default 0


def test_engine_stats_carries_kernel_impl_across_regions():
    rg, assign = region_line(2, 4, seed=3)
    cp = ControlPlane(rg, region_of=assign, micro_batch=4, seed=3, **PYM)
    cp.register_tenant("a", weight=1.0)
    for i in range(4):
        cp.submit("a", random_dataflow(rg, 3, seed=70 + i,
                                       creq_range=(0.05, 0.2),
                                       breq_range=(0.5, 2.0)))
    cp.pump(rounds=3)
    # pin distinct per-region backends: the cross-region fold must carry
    # them as a consensus label instead of last-writer-wins (or dropping
    # them to the zero default, the bug this fixes)
    cp.regions[0].placer.stats.kernel_impls["ref"] = 2
    cp.regions[1].placer.stats.kernel_impls["pallas"] = 1
    s = cp.engine_stats()
    assert s.kernel_impl.startswith("mixed(")
    assert "ref" in s.kernel_impl and "pallas" in s.kernel_impl
    assert s.solve_n > 0

    # consensus collapses when every region agrees
    cp.regions[1].placer.stats.kernel_impls = {"ref": 1}
    assert cp.engine_stats().kernel_impl == "ref"


@pytest.mark.parametrize("plane", ["centralized", "regional", "hierarchical"])
def test_reconstruction_fallbacks_counted_at_every_plane_level(
        plane, monkeypatch):
    """A device answer whose parent pointers do not backtrack is re-solved
    by the host solver; every such rescue reaches ``OnlineStats``,
    ``engine_stats()`` and the metrics registry at each plane level."""
    import repro.core.reconstruct as reconstruct

    monkeypatch.setattr(reconstruct, "backtrack",
                        lambda *a, **kw: (None, [], False))
    jm = dict(method="leastcost_jax", micro_batch=4)
    if plane == "centralized":
        rg = waxman(10, seed=3)
        cp = ControlPlane(rg, **jm)
    elif plane == "regional":
        rg, assign = region_line(2, 4, seed=2)
        cp = ControlPlane(rg, region_of=assign, seed=2, **jm)
    else:
        rg, assign = region_tree(2, 2, 3, seed=1)
        cp = ControlPlane(rg, region_of=assign, levels=2, branching=2,
                          seed=1, **jm)
    cp.register_tenant("a", weight=1.0)
    for i in range(4):
        cp.submit("a", random_dataflow(rg, 3, seed=60 + i,
                                       creq_range=(0.05, 0.2),
                                       breq_range=(0.5, 2.0)))
    cp.pump(rounds=3)
    s = cp.engine_stats()
    assert s.fallbacks > 0
    assert cp.metrics_registry().total("placer.fallbacks") == s.fallbacks


def test_consensus_impl_labels_mixed_backends():
    assert ControlPlane._consensus_impl({"ref": 3}) == "ref"
    mixed = ControlPlane._consensus_impl({"ref": 2, "pallas": 5})
    assert mixed.startswith("mixed(") and "ref" in mixed and "pallas" in mixed
    assert ControlPlane._consensus_impl({}) == ""


# ---------------------------------------------------------------------------
# plane metrics registries
# ---------------------------------------------------------------------------


def test_plane_metrics_registry_centralized():
    cp = _pumped_plane()
    snap = cp.metrics_registry().snapshot()
    json.dumps(snap)  # must serialize into bench records
    assert snap["timing.solve_ms"] > 0.0
    assert any(k.startswith("placer.") for k in snap)


def test_plane_metrics_registry_merges_regions_with_labels():
    rg, assign = region_line(2, 4, seed=4)
    cp = ControlPlane(rg, region_of=assign, micro_batch=4, seed=4, **PYM)
    cp.register_tenant("a", weight=1.0)
    for i in range(4):
        cp.submit("a", random_dataflow(rg, 3, seed=90 + i,
                                       creq_range=(0.05, 0.2),
                                       breq_range=(0.5, 2.0)))
    cp.pump(rounds=3)
    reg = cp.metrics_registry()
    # per-region series are tagged with their plane position
    planes = {dict(lbl).get("plane")
              for lbl in reg.labeled("placer.admitted")}
    assert planes <= {"r0", "r1"} and planes
    assert reg.total("gossip.messages_sent") >= 0.0
    json.dumps(reg.snapshot())


# ---------------------------------------------------------------------------
# gossip windowed snapshot — satellite 2
# ---------------------------------------------------------------------------


def test_gossip_snapshot_windowing_preserves_lifetime():
    rg, assign = region_line(2, 4, seed=5)
    cp = ControlPlane(rg, region_of=assign, micro_batch=4, fanout=1,
                      seed=5, **PYM)
    cp.register_tenant("a", weight=1.0)
    cp.submit("a", _unit_df())
    cp.pump(rounds=3)
    bus = cp.bus
    life1 = bus.gossip_stats()
    w1 = bus.snapshot(reset=True)
    assert w1["messages_sent"] == life1["messages_sent"]
    # a fresh window starts at zero...
    assert bus.snapshot()["messages_sent"] == 0
    cp.pump(rounds=2)
    w2 = bus.snapshot(reset=True)
    assert w2["messages_sent"] > 0
    # ...while the lifetime counters never rewind
    life2 = bus.gossip_stats()
    assert life2["messages_sent"] == life1["messages_sent"] + w2["messages_sent"]


def test_gossip_bus_snapshot_unit():
    bus = GossipBus(3, fanout=1, seed=0)
    for _ in range(2):
        bus.tick()
    assert bus.snapshot()["rounds"] == 2
    bus.snapshot(reset=True)
    assert bus.snapshot()["rounds"] == 0
    assert bus.gossip_stats()["rounds"] == 2


# ---------------------------------------------------------------------------
# profiler sink and the inner-boundary counters
# ---------------------------------------------------------------------------

JM = dict(method="leastcost_jax")  # the batched DP: dispatch/finalize path


def _requests(rg, k, seed):
    return [random_dataflow(rg, 3, seed=seed + i, creq_range=(0.05, 0.2),
                            breq_range=(0.5, 2.0)) for i in range(k)]


def test_spans_cost_nothing_without_a_profiler_session():
    from jax.profiler import TraceAnnotation

    assert not TraceAnnotation.is_enabled()
    a = NULL.span("validate.commit", track="placer")
    assert a is NULL.span("rebuild", track="residual", site="commit")
    sp = Tracer().span("rebuild", track="residual")
    with sp:
        assert sp._ann is None  # no profiler annotation opened


def _host_events(trace_dir):
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, line.name)
                    for e in line.events]
    return out


def test_profiler_sink_nests_program_spans_in_the_device_trace(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    rg = waxman(10, seed=3)
    cp = ControlPlane(rg, micro_batch=4, **JM)
    cp.register_tenant("a", weight=1.0)
    for df in _requests(rg, 3, 40):
        cp.submit("a", df)
    scoped = Tracer().scoped("r0")
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("outer"):
            cp.pump()
            with scoped.span("round", track="plane"):
                pass
    evs = _host_events(tmp_path)
    (outer,) = [e for e in evs if e[0] == "outer"]
    for name in ("plane.pump.round", "placer.dispatch", "placer.dp_wait",
                 "placer.reconstruct", "placer.validate.commit",
                 "residual.rebuild", "residual.sync", "r0/plane.round"):
        mine = [e for e in evs if e[0] == name]
        assert mine, name
        for _, s, t, line in mine:
            # same thread line as the enclosing annotation, inside it
            assert line == outer[3] and outer[1] <= s <= t <= outer[2], name
    # once the session stops the sink is off again
    assert NULL.span("x") is NULL.span("y")


def test_rebuilds_are_counted_per_call_site():
    rg = waxman(10, seed=3)
    cp = ControlPlane(rg, **JM)
    placer = cp.placer
    (t,) = placer.admit_many(_requests(rg, 1, 40))
    assert t is not None
    counts = {site: c for site, (c, _) in placer.res.rebuilds.items()}
    # classify the batch, upload the mirror, dispatch, then validate before
    # and after the one commit
    assert counts == {"classify": 1, "upload": 1, "dispatch": 1, "commit": 2}
    for _ in range(3):
        placer.residual_graph("conflict")
    placer.residual_graph()
    reg = cp.metrics_registry()
    assert reg.get("residual.rebuilds", site="conflict") == 3.0
    assert reg.get("residual.rebuilds", site="other") == 1.0
    assert reg.get("residual.rebuilds", site="commit") == 2.0
    assert reg.get("residual.rebuild_ms", site="commit") > 0.0
    assert reg.get("residual.full_uploads") == 1.0
    assert reg.get("residual.sync_ms") > 0.0


def test_dp_wait_and_reconstruct_lie_inside_each_solve():
    rg = waxman(10, seed=6)
    placer = OnlinePlacer(rg, **JM)
    seen = []
    note = placer._note_solve

    def spy(st, **kw):
        seen.append(st)
        note(st, **kw)

    placer._note_solve = spy
    for k in range(3):
        placer.admit_many(_requests(rg, 4, 500 + 10 * k))
    batched = [st for st in seen if st.dp_wait_ms > 0.0]
    assert batched
    for st in seen:
        assert st.reconstruct_ms >= 0.0
        assert st.dp_wait_ms + st.reconstruct_ms <= st.solve_ms
    reg = MetricsRegistry()
    absorb_online_stats(reg, placer.stats)
    assert reg.get("timing.dp_wait_ms") == pytest.approx(
        sum(st.dp_wait_ms for st in seen))
    assert reg.get("timing.reconstruct_ms") == pytest.approx(
        sum(st.reconstruct_ms for st in seen))


def test_queue_wait_has_one_sample_per_dispatch():
    rg = waxman(10, seed=3)
    tr = Tracer()
    cp = ControlPlane(rg, micro_batch=2, max_attempts=3, preempt=False,
                      tracer=tr, **PYM)
    cp.register_tenant("a", weight=1.0)
    for df in _requests(rg, 3, 40):
        cp.submit("a", df)
    # fits nowhere: rejected, requeued and dispatched again until dropped
    cp.submit("a", DataflowPath.make([0.0, 1e6, 0.0], [1.0, 1.0], 0, 2))
    cp.pump(rounds=8)
    dispatches = [e for e in tr.events
                  if e["ph"] == "n" and e["name"] == "dispatch"]
    assert len(dispatches) == 3 + 3  # the infeasible one three times
    assert cp.queue_wait_ms.count == len(dispatches)
    snap = cp.metrics_registry().snapshot()
    assert snap["plane.queue_wait_ms"]["count"] == len(dispatches)
    assert snap["plane.queue_wait_ms"]["min"] >= 0.0


def test_remap_ms_counts_only_failures_that_displace():
    rg = waxman(10, seed=6)
    placer = OnlinePlacer(rg, **PYM)
    tickets = [t for t in placer.admit_many(_requests(rg, 3, 500)) if t]
    used = {v for t in tickets for v in t.mapping.route}
    idle = [v for v in range(rg.n) if v not in used]
    assert idle and tickets
    placer.fail_node(idle[0])
    assert placer.stats.remap_ms == 0.0
    reg = MetricsRegistry()
    absorb_online_stats(reg, placer.stats)
    assert reg.get("placer.remap_ms") is None
    victim = tickets[0].mapping.route[1]
    remapped, dropped = placer.fail_node(victim)
    assert remapped or dropped
    assert placer.stats.remap_ms > 0.0
    assert placer.stats.remapped + placer.stats.dropped == (
        len(remapped) + len(dropped))


def test_vmapped_dp_module_has_a_stable_name():
    from repro.core.leastcost import _vmapped_dp
    from repro.core.problem import stack_requests

    rg = waxman(8, seed=1)
    tensors, p = stack_requests(rg, _requests(rg, 2, 7))
    hlo = _vmapped_dp(rg.n, p, rg.n - 1).lower(tensors).as_text()
    assert "jit_vmapped_leastcost_dp" in hlo
    assert "lambda" not in hlo.split("\n", 1)[0]
