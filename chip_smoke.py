"""Bring-up smoke test: the placement control plane's main path on one TPU.

Drives ``ControlPlane.submit`` -> ``pump`` -> ``OnlinePlacer.admit_many``
-> the batched (min,+) LeastCostMap DP on the chip -> host reconstruction
and commit, over one seeded trace (``benchmarks/bench_trace.build_trace``
with Zipf-skewed tenants: p in 3..5, three preemption classes, a burst of
node failures restored three rounds later) on ``region_tree(3, 4, 64)``
(n = 4096), through three planes:

  A  ``ControlPlane(rg)`` with its defaults (vmapped DP), after ``warmup()``;
  B  the same plane with ``use_kernel=True`` (compiled Pallas kernel);
  C  ``ControlPlane(rg, region_of=..., levels=2, branching=8)``: many small
     per-region device DPs.

Every phase must keep its invariants and ticket ledger, need no host rescue
of a device answer (reconstruction fallbacks == 0), and agree with the host
solver (``method="leastcost_python"``) run through the same plane class:
the same admitted requests after every pump round, and the same placement
costs within ``EPS_COST``.  B must equal A bit for bit.  The host reference
of the flat plane takes seconds per request at n = 4096, so it covers the
first rounds up to ``REF_SUBMISSIONS`` submissions; the hierarchy's
reference covers the whole stream.  Both run in CPU-only worker processes
while the chip works.

Usage:  python chip_smoke.py

Phase timings go to earlier lines; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Exits non-zero, printing no result, when JAX finds no TPU or a phase fails.
"""
from __future__ import annotations

import json
import multiprocessing
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
LEAF_NODES = 64  # region_tree(3, 4, 64): 64 fully meshed leaves, n = 4096
TOPOLOGY_SEED = 11
STREAM = dict(rounds=10, warmup=5, base_rate=20.0, churn_period=5,
              churn_down=3)
TENANT_ZIPF = 1.1
REF_SUBMISSIONS = 64
REF_TIMEOUT_S = 900.0


def _plane_kwargs(phase: str, assign) -> dict:
    return {
        "A": {},
        "B": {"use_kernel": True},
        "C": {"region_of": assign, "levels": 2, "branching": 8},
    }[phase]


def build_stream(leaf_nodes: int, stream: dict):
    """The network and the seeded trace every phase replays; tenants are
    redrawn Zipf(``TENANT_ZIPF``)-skewed over the trace's tenant names."""
    import numpy as np

    from benchmarks.bench_trace import TENANTS, build_trace
    from repro.core import region_tree

    rg, assign = region_tree(3, 4, leaf_nodes, seed=TOPOLOGY_SEED)
    events, churn = build_trace(rg.n, assign, 4, seed=TOPOLOGY_SEED + 1,
                                **stream)
    w = 1.0 / np.arange(1, len(TENANTS) + 1) ** TENANT_ZIPF
    rng = np.random.default_rng(TOPOLOGY_SEED + 2)
    for ev, i in zip(events, rng.choice(len(TENANTS), len(events),
                                        p=w / w.sum())):
        ev["tenant"] = TENANTS[i]
    return rg, assign, events, churn


def ref_rounds(events, limit: int) -> int:
    """Fewest leading rounds that hold ``limit`` submissions."""
    seen = 0
    for t in range(max(ev["round"] for ev in events) + 1):
        seen += sum(1 for ev in events if ev["round"] == t)
        if seen >= limit:
            return t + 1
    return t + 1


def replay(cp, events, churn, rounds: int) -> list[dict]:
    """Drive ``cp`` through the first ``rounds`` rounds of the trace.

    Returns one record per round, taken after its ``pump``: the sorted
    active request ids, and every committed ticket's (cost, assign, route)
    across the plane's leaf placers, sorted."""
    from benchmarks.bench_trace import TENANTS

    for t in TENANTS:
        cp.register_tenant(t, weight=1.0)
    leaves = [p for _, p in cp.leaf_planes()]
    expiry: dict[int, int] = {}
    record = []
    for t in range(rounds):
        for r, kind, nodes in churn:
            if r == t:
                for v in nodes:
                    cp.fail_node(v) if kind == "fail" else cp.restore_node(v)
        for ev in events:
            if ev["round"] == t:
                rid = cp.submit(ev["tenant"], ev["df"], klass=ev["klass"])
                expiry[rid] = t + ev["hold"]
        cp.pump(rounds=1)
        active = cp.active_ids()
        record.append({
            "active": active,
            "tickets": sorted(
                (tk.mapping.cost, tk.mapping.assign, tk.mapping.route)
                for p in leaves for tk in p.placer.tickets.values()),
        })
        live = set(active)
        for rid in [r for r, e in expiry.items() if e <= t and r in live]:
            cp.release(rid)
            del expiry[rid]
    cp.check_invariants()
    ledger = cp.conservation()
    if not ledger["ok"]:
        raise AssertionError(f"ticket ledger broken: {ledger}")
    return record


def reference(phase: str, leaf_nodes: int, stream: dict, rounds: int):
    """The phase's plane class on the host solver, over ``rounds`` rounds."""
    from repro.service import ControlPlane

    rg, assign, events, churn = build_stream(leaf_nodes, stream)
    kw = _plane_kwargs(phase, assign)
    kw.pop("use_kernel", None)  # a host solver has no kernel
    cp = ControlPlane(rg, method="leastcost_python", **kw)
    return replay(cp, events, churn, rounds)


def compare(name: str, got: list[dict], ref: list[dict]) -> None:
    """Same admitted requests and placement costs in every reference round."""
    from repro.core.problem import EPS_COST

    for t, (g, r) in enumerate(zip(got, ref)):
        if g["active"] != r["active"]:
            raise AssertionError(
                f"{name}: round {t} admitted sets differ: "
                f"{sorted(set(g['active']) ^ set(r['active']))[:10]}")
        gc = [c for c, *_ in g["tickets"]]
        rc = [c for c, *_ in r["tickets"]]
        if len(gc) != len(rc) or any(
                abs(a - b) > EPS_COST for a, b in zip(gc, rc)):
            raise AssertionError(f"{name}: round {t} placement costs differ")


class CompileLog:
    """Counts backend compiles and persistent-cache hits via jax.monitoring."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_s, self.cache_hits


def run_phase(phase: str, rg, assign, events, churn, stream: dict, *,
              expect_impl: str, log: CompileLog) -> tuple[list, dict]:
    """Build the phase's plane, warm it, replay the stream, check it."""
    from repro.service import ControlPlane

    c0 = log.snapshot()
    t0 = time.perf_counter()
    cp = ControlPlane(rg, method="leastcost_jax",
                      **_plane_kwargs(phase, assign))
    cp.warmup()
    setup_s = time.perf_counter() - t0
    c1 = log.snapshot()
    t1 = time.perf_counter()
    record = replay(cp, events, churn, stream["rounds"])
    stream_s = time.perf_counter() - t1
    c2 = log.snapshot()
    es = cp.engine_stats()
    if es.fallbacks:
        raise AssertionError(
            f"phase {phase}: {es.fallbacks} device answers needed the host "
            "reconstruction fallback")
    if es.kernel_impl != expect_impl:
        raise AssertionError(f"phase {phase}: kernel_impl "
                             f"{es.kernel_impl!r}, expected {expect_impl!r}")
    ledger = cp.conservation()
    summary = {
        "phase": phase,
        "n": rg.n,
        "setup_s": setup_s,
        "setup_compiles": c1[0] - c0[0],
        "setup_compile_s": c1[1] - c0[1],
        "setup_cache_hits": c1[2] - c0[2],
        "stream_s": stream_s,
        "stream_compiles": c2[0] - c1[0],
        "stream_compile_s": c2[1] - c1[1],
        "submitted": ledger["submitted"],
        "active_end": ledger["active"],
        "queued_end": ledger["queued"],
        "dropped": ledger["dropped"],
        "solves": int(cp.metrics_registry().total("placer.solves")),
        # host clock: solve = DP dispatch + wait + reconstruction
        "solve_ms": es.solve_ms,
        "overhead_ms": es.overhead_ms,
        "conflict_resolve_ms": es.conflict_resolve_ms,
        "fallbacks": es.fallbacks,
        "kernel_impl": es.kernel_impl,
    }
    print(json.dumps(summary), flush=True)
    return record, summary


def run_smoke(*, leaf_nodes: int = LEAF_NODES, stream: dict = STREAM,
              ref_submissions: int = REF_SUBMISSIONS, kernel_impl: str,
              pool=None) -> dict:
    """Phases A, B, C and their host references; raises on any failure.

    ``pool`` (a ``multiprocessing`` pool of CPU-only workers) runs the
    references while the device phases run; without one they run inline
    afterwards."""
    rg, assign, events, churn = build_stream(leaf_nodes, stream)
    flat_rounds = ref_rounds(events, ref_submissions)
    jobs = {"flat": ("A", leaf_nodes, stream, flat_rounds),
            "hier": ("C", leaf_nodes, stream, stream["rounds"])}
    pending = ({k: pool.apply_async(reference, a) for k, a in jobs.items()}
               if pool is not None else {})
    print(json.dumps({"n": rg.n, "submissions": len(events),
                      "rounds": stream["rounds"], "churn": len(churn),
                      "flat_ref_rounds": flat_rounds}), flush=True)
    log = CompileLog()
    recs = {}
    for phase, impl in (("A", ""), ("B", kernel_impl), ("C", "")):
        recs[phase], _ = run_phase(phase, rg, assign, events, churn, stream,
                                   expect_impl=impl, log=log)
    if recs["B"] != recs["A"]:
        raise AssertionError("phase B (kernel) differs from phase A")
    t0 = time.perf_counter()
    refs = {k: (pending[k].get(REF_TIMEOUT_S) if pending
                else reference(*a)) for k, a in jobs.items()}
    compare("A", recs["A"], refs["flat"])
    compare("B", recs["B"], refs["flat"])
    compare("C", recs["C"], refs["hier"])
    result = {
        "reference": {
            "A": f"first {flat_rounds} rounds "
                 f"({sum(1 for e in events if e['round'] < flat_rounds)} "
                 "submissions)",
            "B": "as A",
            "C": f"whole stream ({len(events)} submissions)",
        },
        "reference_wait_s": time.perf_counter() - t0,
        "compiles": log.compiles,
        "compile_s": log.compile_s,
        "cache_hits": log.cache_hits,
    }
    print(json.dumps(result), flush=True)
    return result


def _host_only():
    """Worker initializer: the host reference never touches the chip."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke.py must run from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX runs on {dev.platform!r}", file=sys.stderr)
        return 1
    from repro.core.device import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(2, initializer=_host_only) as pool:
        run_smoke(kernel_impl="pallas", pool=pool)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
