"""One run of one cell: build, warm, pre-load, measure, check, report."""
from __future__ import annotations

import dataclasses
import math
import os
import sys
import time

import numpy as np

from . import audit, catalog, devtrace, loop, network, stats, traffic
from .compile_log import CompileLog
from .peaks import peak

# The control: the reference with its relaxation cut to this many
# supersteps (bounding the rounds is the shortcut a faster DP would take),
# put in the program's place.  Two rounds miss the 3-hop routes between
# sibling leaves and the longer ones across the tree.
CONTROL_SUPERSTEPS = 2


def build_plane(config: dict, net: network.Network):
    from repro.core.graph import ResourceGraph
    from repro.service import ControlPlane

    kw = dict(config["plane"])
    if kw.pop("region_of", None) == "leaf":
        kw["region_of"] = net.leaf_of
    return ControlPlane(ResourceGraph(net.cap, net.bw, net.lat), **kw)


def make_df(req):
    from repro.core.graph import DataflowPath

    return DataflowPath(req.creq, req.breq, int(req.src), int(req.dst))


def counters(cp) -> dict:
    """The program's own counters (``metrics_registry`` snapshot): counter
    values, and the superstep histograms as ``(solves, supersteps)``."""
    out = {}
    for key, v in cp.metrics_registry().snapshot().items():
        if isinstance(v, dict):
            out[key] = (v["count"], v["sum"])
        else:
            out[key] = v
    return out


def delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        b = before.get(k)
        if isinstance(v, tuple):
            b = b or (0, 0.0)
            out[k] = (v[0] - b[0], v[1] - b[1])
        elif isinstance(v, (int, float)):
            out[k] = v - (b or 0.0)
    return out


def limits() -> dict:
    """Each number compared, with its limit: a run is correct iff every
    number is at most its limit."""
    return {"invalid": 0, "wrong_costs": 0, "wrong_drops": 0,
            "undecided": 0, "fallbacks": 0, "ledger_broken": 0}


def judge(readings: audit.Readings, fallbacks: int, ledger_broken: int):
    nums = {"invalid": readings.invalid, "wrong_costs": readings.wrong_costs,
            "wrong_drops": readings.wrong_drops,
            "undecided": readings.undecided, "fallbacks": fallbacks,
            "ledger_broken": ledger_broken}
    lim = limits()
    ok = all(nums[k] <= lim[k] for k in lim) and readings.checked_cost > 0
    return ok, {k: [nums[k], lim[k]] for k in lim}


@dataclasses.dataclass
class Measured:
    """What one run of a cell leaves for the check and the report."""

    bench: dict
    cell: str
    seed: int
    trace: bool
    net: network.Network
    first_cost: object  # the configuration's reference (catalog.reference)
    rec: loop.Record
    window_rids: list
    counters: dict  # program counters over the window
    spans_s: dict  # harness host spans over the window
    fallbacks: int
    ledger_broken: int
    setup_s: float
    device: dict
    trace_dir: str
    diag: dict


def measure(cell_name: str, *, seed: int, seconds: float, trace: bool,
            t_start: float, bench: dict | None = None,
            config: dict | None = None, mix: dict | None = None,
            out_dir: str | None = None, log=sys.stderr) -> Measured:
    """Build, warm, pre-load, run the window and the drain.  Tests pass
    ``bench``/``config``/``mix`` directly."""
    import jax

    bench = bench if bench is not None else catalog.benchmark()
    cell = catalog.cell(bench, cell_name)
    config = config if config is not None else catalog.config(
        bench, cell["config"])
    mix = mix if mix is not None else catalog.traffic(cell["traffic"])
    reader = catalog.reader(config)
    first_cost = catalog.reference(config)
    out_dir = out_dir or os.path.join(catalog.ROOT, ".bench_out")
    devices = jax.devices()
    dev = devices[0]
    clog = CompileLog()

    phases = {"start": time.perf_counter() - t_start}
    net = network.build(config["network"])
    sched = traffic.build(mix, net, seconds=seconds, seed=seed,
                          standing=int(config["standing"]["count"]))
    cp = build_plane(config, net)
    rec = loop.Record()
    drv = loop.PlaneDriver(cp, rec, reader)
    drv.register(mix["tenants"])
    phases["plane"] = time.perf_counter() - t_start
    for p in range(mix["p"][0], mix["p"][1] + 1):
        cp.warmup(p=p)
    phases["warmup"] = time.perf_counter() - t_start
    loop.preload(drv, sched.standing, make_df)
    standing_live = len(reader.live(cp))
    util = reader.committed_share(cp)
    setup = clog.snapshot()
    setup_s = time.perf_counter() - t_start
    c0 = counters(cp)
    spans0 = dict(rec.span_s)
    n_events0 = len(rec.events)
    n_pumps0 = len(rec.pump_log)
    at_close = {}

    def close():
        at_close["counters"] = delta(counters(cp), c0)
        at_close["spans_s"] = {k: v - spans0.get(k, 0.0)
                               for k, v in rec.span_s.items()}
        at_close["compiles"] = clog.snapshot()

    tdir = os.path.join(out_dir, "trace")
    if trace:
        with devtrace.capture(tdir):
            window_rids = loop.run(drv, sched, make_df, seconds,
                                   on_close=close)
    else:
        window_rids = loop.run(drv, sched, make_df, seconds, on_close=close)
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}

    ledger_broken = 0
    try:
        cp.check_invariants()
    except AssertionError as e:
        ledger_broken = 1
        print(f"invariants: {e}", file=log)
    if not cp.conservation()["ok"]:
        ledger_broken = 1
    diag = {
        "cell": cell_name, "seed": seed, "n": net.n, "setup_phases_s": phases,
        "standing_live": standing_live, "standing_utilisation": util,
        "setup_compiles": setup, "window_compiles": {
            k: at_close["compiles"][k] - setup[k] for k in setup},
        "pumps": rec.pumps, "events": len(rec.events) - n_events0,
        "pump_log": rec.pump_log[n_pumps0:],
        "submit_lag_max_s": max(rec.submit_lag_s, default=0.0),
    }
    return Measured(bench, cell_name, seed, trace, net, first_cost, rec,
                    window_rids, at_close["counters"], at_close["spans_s"],
                    int(cp.engine_stats().fallbacks), ledger_broken, setup_s,
                    device, tdir, diag)


def report(m: Measured, *, control: int | None = None,
           log=sys.stderr) -> dict:
    """Check the run against the reference and build the result line's
    object.  ``control`` puts the reference, its relaxation cut to that
    many supersteps, in the program's place: the same check then has to
    come out false."""
    rec, window_rids = m.rec, m.window_rids
    window_s = rec.window_end
    due = [rec.due[r] for r in window_rids]
    dec = [rec.decided.get(r, (None, ""))[0] for r in window_rids]
    lat = stats.latencies_ms(due, dec, rec.drain_end)
    decided_in = [r for r, t in zip(window_rids, dec)
                  if t is not None and t <= window_s]
    undecided_end = len(window_rids) - len(decided_in)
    dropped = sum(1 for r in window_rids
                  if rec.decided.get(r, (0, ""))[1] == "drop")
    never = sum(1 for r in window_rids if r not in rec.decided)

    t_audit = time.perf_counter()
    readings = audit.replay(m.net, rec.requests, rec.events,
                            first_cost=m.first_cost, undecided=never,
                            control_supersteps=control)
    audit_s = time.perf_counter() - t_audit
    ok, compared = judge(readings, m.fallbacks, m.ledger_broken)

    ctx = {
        "window_s": window_s,
        "latencies_ms": lat,
        "decisions": len(decided_in),
        "setup_s": m.setup_s,
        "counters": m.counters,
        "spans_s": m.spans_s,
        "n": m.net.n,
        "device_kind": m.device["kind"],
        "trace": None,
    }
    device = dict(m.device)
    result_breakdown = None
    if m.trace:
        red = devtrace.reduce(devtrace.read(m.trace_dir))
        ctx["trace"] = red
        ctx["peak"] = (peak(device["kind"]) if device["platform"] == "tpu"
                       else None)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result_breakdown = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    kind = "per_layer" if m.trace else "end_to_end"
    metrics = {}
    for e in catalog.metrics_of(m.bench, m.cell, kind):
        v = catalog.metric_reader(e["name"])(ctx)
        if v is not None:
            metrics[e["name"]] = {"value": float(v), "unit": e["unit"]}
    result = {
        "correct": bool(ok),
        "attempted": len(window_rids),
        # a request fails when it is dropped or never decided; one decided
        # late, in the drain, is late and counted so in the latencies
        "failed": dropped + never,
        "metrics": metrics,
        "device": device,
    }
    if result_breakdown is not None:
        result["breakdown"] = result_breakdown
    diag = dict(m.diag, control=control, decided_in_window=len(decided_in),
                dropped=dropped, undecided_at_close=undecided_end,
                drain_s=rec.drain_end - window_s, audit_s=audit_s,
                readings=vars(readings), latencies_ms=sorted(lat.tolist()),
                spans_s=m.spans_s)
    print("diag " + _json(diag), file=log)
    result["compared"] = compared
    return result


def run(cell_name: str, *, seed: int, seconds: float, trace: bool,
        t_start: float, log=sys.stderr, **kw) -> dict:
    """Run ``cell_name`` once; returns the result line's object."""
    return report(measure(cell_name, seed=seed, seconds=seconds, trace=trace,
                          t_start=t_start, log=log, **kw), log=log)


def _json(obj) -> str:
    import json

    def fix(x):
        if isinstance(x, float) and not math.isfinite(x):
            return str(x)
        if isinstance(x, dict):
            return {str(k): fix(v) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            return [fix(v) for v in x]
        if isinstance(x, np.generic):
            return x.item()
        return x

    return json.dumps(fix(obj))
