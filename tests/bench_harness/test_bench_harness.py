"""The harness driven end to end on the CPU at a tiny size: a sound run is
correct, the comparison fails on a perturbed decision or cost, on each
fault the timed path can have, and on the control; the entry point refuses
to run without a TPU or without the program."""
import copy
import io
import dataclasses
import os
import re
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from bench.harness import audit, catalog, cell, loop

ROOT = catalog.ROOT


def _cell(mix_name="steady_flat4k", **locality):
    bench = catalog.benchmark()
    config = catalog.config(bench, "flat4k")
    config["network"].update(levels=3, branching=2, leaf_nodes=4)
    config["standing"]["count"] = 4
    mix = catalog.traffic(mix_name)
    mix["arrivals"]["rate_per_s"] = 12.0
    mix["p"] = [3, 4]
    mix["block_leaves"] = 2
    mix["churn"].update(nodes=2, down_s=0.5)
    if locality:
        mix["locality"] = locality
    return bench, config, mix


def _measure(cell_name="flat4k-steady", seconds=2.0, seed=2**31 + 11,
             **locality):
    bench, config, mix = _cell(**locality)
    return cell.measure(cell_name, seed=seed, seconds=seconds, trace=False,
                        t_start=time.perf_counter(), bench=bench,
                        config=config, mix=mix, out_dir=None,
                        log=io.StringIO())


def _run(**kw):
    return cell.report(_measure(**kw), log=io.StringIO())


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["compared"]
    assert r["attempted"] > 10
    assert set(r["metrics"]) == {"decision_p50_ms", "setup_s"}
    assert list(r)[-1] == "compared"
    assert all(v <= lim for v, lim in r["compared"].values())


def test_control_fails_the_comparison():
    # every request crosses the top of the tree: routes of 4+ hops, which
    # a relaxation cut to CONTROL_SUPERSTEPS supersteps cannot reach
    m = _measure(seed=7, leaf=0.0, block=0.0, any=1.0)
    program = cell.report(m, log=io.StringIO())
    assert program["correct"], program["compared"]
    control = cell.report(m, control=cell.CONTROL_SUPERSTEPS,
                          log=io.StringIO())
    assert control["correct"] is False
    wrong, limit = control["compared"]["wrong_costs"]
    assert wrong > limit


def _record(bench, config, mix):
    from bench.harness import network, traffic

    net = network.build(config["network"])
    sched = traffic.build(mix, net, seconds=2.0, seed=7, standing=4)
    cp = cell.build_plane(config, net)
    rec = loop.Record()
    drv = loop.PlaneDriver(cp, rec, catalog.reader(config))
    drv.register(mix["tenants"])
    loop.preload(drv, sched.standing, cell.make_df)
    loop.run(drv, sched, cell.make_df, 2.0)
    return {"net": net, "requests": rec.requests, "events": rec.events,
            "first_cost": catalog.reference(config)}


def _perturbed(rec, field):
    events = copy.copy(rec["events"])
    for i, ev in enumerate(events):
        if ev[0] == "pump" and ev[1]:
            rid = max(ev[1], key=lambda r: ev[1][r].order)
            pl = ev[1][rid]
            if field == "cost":
                bad = dataclasses.replace(pl, cost=pl.cost + 1.0)
            else:
                bad = dataclasses.replace(pl, route=pl.route[:1] + pl.route)
            snap = dict(ev[1])
            snap[rid] = bad
            events[i] = ("pump", snap, ev[2])
            # later snapshots still hold the original: replace it there too
            for j in range(i + 1, len(events)):
                if events[j][0] in ("pump", "fail"):
                    s = events[j][1] if events[j][0] == "pump" else events[j][2]
                    if s.get(rid) is pl:
                        s = dict(s)
                        s[rid] = bad
                        events[j] = ((events[j][0], s, events[j][2])
                                     if events[j][0] == "pump"
                                     else (events[j][0], events[j][1], s))
            return events
    raise AssertionError("no placement recorded")


@pytest.mark.parametrize("field", ["cost", "route"])
def test_comparison_fails_on_a_perturbed_decision(field):
    bench, config, mix = _cell()
    rec = _record(bench, config, mix)
    sound = audit.replay(rec["net"], rec["requests"], rec["events"],
                         first_cost=rec["first_cost"], undecided=0)
    assert sound.invalid == 0 and sound.checked_cost > 0
    bad = audit.replay(rec["net"], rec["requests"], _perturbed(rec, field),
                       first_cost=rec["first_cost"], undecided=0)
    assert bad.invalid > 0
    ok, _ = cell.judge(bad, 0, 0)
    assert not ok


def test_comparison_fails_on_a_wrong_drop():
    bench, config, mix = _cell()
    rec = _record(bench, config, mix)
    events = list(rec["events"])
    i = next(i for i, ev in enumerate(events) if ev[0] == "pump" and ev[1])
    rid = next(iter(events[i][1]))
    events[i] = ("pump", events[i][1], [rid])
    bad = audit.replay(rec["net"], rec["requests"], events,
                       first_cost=rec["first_cost"], undecided=0)
    assert bad.wrong_drops == 1
    assert not cell.judge(bad, 0, 0)[0]


@pytest.fixture(scope="module")
def measured():
    return _measure()


def _replay(m, first_cost, control=None):
    return audit.replay(m.net, m.rec.requests, m.rec.events,
                        first_cost=first_cost, undecided=0,
                        control_supersteps=control)


def _least_cost(ref, r, *, max_supersteps=None):
    # LeastCostMap called directly, not through bench/references
    return ref.least_cost(r.creq, r.breq, r.src, r.dst,
                          max_supersteps=max_supersteps)


@pytest.mark.parametrize("control", [None, cell.CONTROL_SUPERSTEPS])
def test_named_leastcost_reads_as_no_reference_named(measured, control):
    _, config, _ = _cell()
    assert "reference" not in config
    unnamed = _replay(measured, catalog.reference(config), control)
    named = _replay(measured, catalog.reference(
        dict(config, reference="leastcost")), control)
    assert unnamed == named == _replay(measured, _least_cost, control)
    assert unnamed.checked_cost > 0


def test_a_reference_promising_another_cost_fails_every_check(measured):
    bad = _replay(measured, lambda ref, r, **kw: _least_cost(ref, r, **kw)
                  + 1.0)
    assert bad.checked_cost > 0 and bad.wrong_costs == bad.checked_cost
    assert not cell.judge(bad, 0, 0)[0]


def test_a_reference_promising_nothing_is_not_correct(measured):
    r = _replay(measured, lambda ref, r, **kw: None)
    assert r.placements > 0 and r.invalid == 0
    assert r.checked_cost == 0 and r.wrong_costs == 0 and r.wrong_drops == 0
    assert not cell.judge(r, 0, 0)[0]


class _RegionalPlane:
    """A plane with neither ``active`` nor ``placer``, as the regional
    planes are: one pump admits the even rids, drops the odd ones and
    reports each drop by its rid."""

    def __init__(self):
        self.on_drop = None
        self.queue: list = []
        self.placed: dict = {}
        self.submitted = 0

    def submit(self, tenant, df, klass=0):
        self.queue.append(self.submitted)
        self.submitted += 1
        return self.queue[-1]

    def conservation(self):
        return {"queued": len(self.queue), "in_flight": 0}

    def pump(self, rounds=1):
        for rid in self.queue:
            if rid % 2:
                self.on_drop(rid)
            else:
                self.placed[rid] = audit.Placement(object(), rid, (rid,),
                                                   (rid,), 0.0)
        self.queue = []

    def release(self, rid):
        del self.placed[rid]


_INT_RID_READER = SimpleNamespace(
    attach=lambda cp, on_drop: setattr(cp, "on_drop", on_drop),
    live=lambda cp: dict(cp.placed),
    is_live=lambda cp, rid: rid in cp.placed)


def test_plane_driver_reads_a_plane_through_its_reader():
    cp = _RegionalPlane()
    rec = loop.Record()
    drv = loop.PlaneDriver(cp, rec, _INT_RID_READER)
    req = SimpleNamespace(tenant="t", klass=0)
    rids = [drv.submit(req, None, 0.0) for _ in range(4)]
    assert drv.queued() == 4
    assert drv.pump(lambda: 1.0)
    assert {r: rec.decided[r] for r in rids} == {
        0: (1.0, "admit"), 1: (1.0, "drop"), 2: (1.0, "admit"),
        3: (1.0, "drop")}
    assert rec.events[-1][0] == "pump" and rec.events[-1][2] == [1, 3]
    # a later pump's drops are its own, not the first pump's
    assert [drv.submit(req, None, 2.0) for _ in range(2)] == [4, 5]
    assert drv.pump(lambda: 3.0)
    assert rec.decided[4] == (3.0, "admit") and rec.decided[5] == (3.0, "drop")
    assert [ev[2] for ev in rec.events] == [[1, 3], [5]]
    assert drv.release(1) is False and drv.release(9) is False
    assert drv.release(0) is True and rec.events[-1] == ("release", 0)
    assert drv.release(0) is False
    assert not hasattr(cp, "active") and not hasattr(cp, "placer")


@pytest.mark.parametrize("key,kind", [("reader", "planes"),
                                      ("reference", "references")])
def test_an_unknown_reader_or_reference_fails_before_the_network(
        monkeypatch, key, kind):
    from bench.harness import network

    bench, config, mix = _cell()
    config[key] = "no_such_module"
    monkeypatch.setattr(network, "build", lambda spec: pytest.fail(
        "the network was built"))
    missing = os.path.join("bench", kind, "no_such_module.py")
    with pytest.raises(FileNotFoundError, match=re.escape(missing)):
        cell.measure("flat4k-steady", seed=3, seconds=1.0, trace=False,
                     t_start=time.perf_counter(), bench=bench, config=config,
                     mix=mix, log=io.StringIO())


def _fault_state_unchanged(monkeypatch):
    from repro.service import ControlPlane

    monkeypatch.setattr(ControlPlane, "pump", lambda self, **kw: [])


def _fault_half_batch_left_out(monkeypatch):
    from repro.service import ControlPlane

    orig = ControlPlane.pump

    def pump(self, **kw):
        for st in self.tenants.values():
            for r in list(st.queue)[1::2]:
                st.queue.remove(r)
        return orig(self, **kw)

    monkeypatch.setattr(ControlPlane, "pump", pump)


def _fault_answer_altered(monkeypatch):
    from repro.core.graph import Mapping
    from repro.core.online import OnlinePlacer

    orig = OnlinePlacer._commit

    def commit(self, df, mapping, **kw):
        return orig(self, df, Mapping(mapping.assign, mapping.route,
                                      mapping.cost + 1.0), **kw)

    monkeypatch.setattr(OnlinePlacer, "_commit", commit)


@pytest.mark.parametrize("fault", [_fault_state_unchanged,
                                   _fault_half_batch_left_out,
                                   _fault_answer_altered])
def test_faults_in_the_timed_path_are_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = _run()
    assert r["correct"] is False


def test_entry_point_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "flat4k-steady",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_entry_point_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in catalog.benchmark()["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "flat4k-steady",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
