"""The harness driven end to end on the CPU at a tiny size: a sound run is
correct, the comparison fails on a perturbed decision or cost, on each
fault the timed path can have, and on the control; the entry point refuses
to run without a TPU or without the program."""
import copy
import io
import dataclasses
import os
import shutil
import subprocess
import sys
import time

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
    assert set(r["metrics"]) == {"decision_p50_ms", "decision_p90_ms",
                                 "setup_s"}
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
    drv = loop.PlaneDriver(cp, rec)
    drv.register(mix["tenants"])
    loop.preload(drv, sched.standing, cell.make_df)
    loop.run(drv, sched, cell.make_df, 2.0)
    return {"net": net, "requests": rec.requests, "events": rec.events}


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
                         undecided=0)
    assert sound.invalid == 0 and sound.checked_cost > 0
    bad = audit.replay(rec["net"], rec["requests"], _perturbed(rec, field),
                       undecided=0)
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
    bad = audit.replay(rec["net"], rec["requests"], events, undecided=0)
    assert bad.wrong_drops == 1
    assert not cell.judge(bad, 0, 0)[0]


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
