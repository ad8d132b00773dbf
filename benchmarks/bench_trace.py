"""Trace replay: the hierarchy's scaling claim under a realistic workload.

One pre-generated trace — heavy-tailed (Pareto-modulated Poisson)
arrivals, a diurnal load sinusoid, 80/15/5 leaf/block/anywhere endpoint
locality, exponential holds, and correlated regional churn (a burst of
co-located node failures, restored a few rounds later) — is replayed
bit-for-bit over a flat regional plane and 2-/3-level hierarchical
planes built on the same ``region_tree`` topology (1k–10k nodes).

Reported per plane: steady-state admission rate, p50/p99 admit latency
in pump rounds, max per-component resident state
(``resident_state_report``), coordination messages per round (gossip +
2PC across every level), drops, and wall clock.  The acceptance gates
(``criterion``) encode the ISSUE's claims: at n >= 1000 the 2-level
plane's max resident component is strictly below the flat plane's,
steady-state admission stays within 5 points, and the smoke run fits
the CI slow-lane wall-clock budget.

    PYTHONPATH=src python benchmarks/bench_trace.py --smoke   # CI, n=1024
    PYTHONPATH=src python benchmarks/bench_trace.py           # adds n=4096
"""
from __future__ import annotations

import json
import time

import numpy as np

from repro.core import DataflowPath, region_line, region_tree
from repro.obs import (
    Tracer,
    reconstruct_request,
    text_timeline,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.service import ControlPlane

TENANTS = ("svc-a", "svc-b", "batch", "edge")
SMOKE_WALLCLOCK_BUDGET_S = 300.0  # measured ~7s locally; CI-CPU headroom


# -- trace generation ---------------------------------------------------------

def build_trace(
    n: int,
    assign: np.ndarray,
    block: int,
    *,
    rounds: int,
    warmup: int,
    base_rate: float,
    hold_mean: float = 8.0,
    churn_period: int = 12,
    churn_down: int = 3,
    seed: int = 0,
):
    """Pre-generate the whole workload; every plane replays it verbatim.

    ``block`` is the leaf-block size for the 15% "nearby" locality class
    (endpoints in sibling leaves under one parent — crosses only the
    lowest cut); 5% of requests pick a uniformly random leaf and may
    cross the top-level cut.
    """
    rng = np.random.default_rng(seed)
    leaves = int(assign.max()) + 1
    k = n // leaves
    events: list[dict] = []
    churn: list[tuple[int, str, list[int]]] = []
    for t in range(rounds):
        diurnal = 1.0 + 0.6 * np.sin(2.0 * np.pi * t / 24.0)
        burst = min(1.0 + float(rng.pareto(2.5)), 8.0)  # heavy tail, capped
        for _ in range(int(rng.poisson(base_rate * diurnal * burst))):
            tenant = TENANTS[int(rng.integers(len(TENANTS)))]
            leaf = int(rng.integers(leaves))
            src = leaf * k + int(rng.integers(k))
            u = float(rng.random())
            if u < 0.80:
                dleaf = leaf
            elif u < 0.95:
                dleaf = (leaf // block) * block + int(rng.integers(block))
            else:
                dleaf = int(rng.integers(leaves))
            dst = dleaf * k + int(rng.integers(k))
            if dst == src:
                dst = dleaf * k + (src - dleaf * k + 1) % k
            p = int(rng.integers(3, 6))
            creq = rng.uniform(0.3, 1.5, size=p).astype(np.float32)
            creq[0] = creq[-1] = 0.0
            breq = rng.uniform(4.0, 18.0, size=p - 1).astype(np.float32)
            events.append({
                "round": t,
                "tenant": tenant,
                "df": DataflowPath(creq, breq, src, dst),
                "hold": max(1, int(rng.exponential(hold_mean))),
                "klass": int(rng.integers(3)),
            })
        # correlated regional churn: a co-located burst in one leaf
        if t >= warmup and t % churn_period == 0:
            leaf = int(rng.integers(leaves))
            down = [leaf * k + i for i in range(max(1, k // 4))]
            churn.append((t, "fail", down))
            restore_at = t + churn_down
            if restore_at < rounds:
                churn.append((restore_at, "restore", down))
    return events, churn


# -- replay -------------------------------------------------------------------

def replay(make_plane, events, churn, *, rounds: int, warmup: int,
           label: str) -> dict:
    t0 = time.perf_counter()
    cp = make_plane()
    for t in TENANTS:
        cp.register_tenant(t, weight=1.0)
    by_round: dict[int, list] = {}
    for ev in events:
        by_round.setdefault(ev["round"], []).append(ev)
    churn_by_round: dict[int, list] = {}
    for r, kind, nodes in churn:
        churn_by_round.setdefault(r, []).append((kind, nodes))

    pending: dict[int, dict] = {}  # rid -> {sub, expiry, adm}
    steady_sub = steady_adm = 0
    latencies: list[int] = []
    for t in range(rounds):
        for kind, nodes in churn_by_round.get(t, []):
            for v in nodes:
                cp.fail_node(v) if kind == "fail" else cp.restore_node(v)
        for ev in by_round.get(t, []):
            rid = cp.submit(ev["tenant"], ev["df"], klass=ev["klass"])
            pending[rid] = {"sub": t, "expiry": t + ev["hold"], "adm": None}
            if t >= warmup:
                steady_sub += 1
        cp.pump(rounds=1)
        active = set(cp.active_ids())
        for rid, info in pending.items():
            if info["adm"] is None and rid in active:
                info["adm"] = t
                if info["sub"] >= warmup:
                    steady_adm += 1
                    latencies.append(t - info["sub"])
        # holds expire relative to the submit round (trace-determined, so
        # identical across planes); an un-admitted rid stays pending and
        # is released on the first round it IS active past expiry
        for rid in [r for r, i in pending.items()
                    if i["expiry"] <= t and r in active]:
            cp.release(rid)
            del pending[rid]

    cp.check_invariants()
    led = cp.conservation()
    cr = cp.coordination_report()
    if "children" in cr:  # hierarchical: totals aggregated over all levels
        msgs = cr["gossip_messages_total"] + cr["twopc_messages_total"]
    else:
        msgs = cr["gossip_messages"] + cr["twopc_messages"]
    # incremental-fast-path columns, summed over every per-region placer
    # through the plane's merged metrics registry (zero when disabled)
    reg = cp.metrics_registry()
    lat = np.asarray(latencies, np.float64)
    return {
        "plane": label,
        "cache_hits": int(reg.total("placer.cache_hits")),
        "cache_misses": int(reg.total("placer.cache_misses")),
        "cache_stale": int(reg.total("placer.cache_stale")),
        "warm_solves": int(reg.total("placer.warm_solves")),
        "steady_submitted": steady_sub,
        "steady_admitted": steady_adm,
        "admission_rate": round(steady_adm / max(steady_sub, 1), 4),
        "p50_admit_rounds": float(np.percentile(lat, 50)) if lat.size else -1.0,
        "p99_admit_rounds": float(np.percentile(lat, 99)) if lat.size else -1.0,
        "max_component_state": cp.resident_state_report()[
            "max_component_state"],
        "max_solve_n": cp.solve_size_report()["max_solve_n"],
        "messages_per_round": round(msgs / rounds, 2),
        "dropped": led["dropped"],
        "conservation_ok": bool(led["ok"]),
        "wallclock_s": round(time.perf_counter() - t0, 2),
    }


# -- scenarios ----------------------------------------------------------------

def run_scenario(levels_phys: int, branching_phys: int, k: int, *,
                 rounds: int, warmup: int, base_rate: float,
                 plane_cfgs, method: str = "leastcost_python",
                 seed: int = 11) -> dict:
    rg, assign = region_tree(levels_phys, branching_phys, k, seed=seed)
    events, churn = build_trace(
        rg.n, assign, branching_phys,
        rounds=rounds, warmup=warmup, base_rate=base_rate, seed=seed + 1,
    )
    planes = []
    for label, kw in plane_cfgs:
        planes.append(replay(
            lambda kw=kw: ControlPlane(
                rg, region_of=assign, method=method, seed=5, **kw),
            events, churn, rounds=rounds, warmup=warmup, label=label,
        ))
    return {
        "n": rg.n,
        "leaf_regions": int(assign.max()) + 1,
        "k": k,
        "rounds": rounds,
        "warmup": warmup,
        "arrivals": len(events),
        "churn_events": len(churn),
        "planes": planes,
    }


def run_json(smoke: bool = False, out_path: str = "BENCH_trace.json") -> dict:
    t0 = time.perf_counter()
    scenarios = []
    # n=1024: 64 16-node leaves; flat R=64 vs 2-level (8x8) vs 3-level (4^3)
    scenarios.append(run_scenario(
        3, 4, 16, rounds=36, warmup=12, base_rate=12.0,
        plane_cfgs=[
            ("flat", {}),
            ("2-level", {"levels": 2, "branching": 8}),
            ("3-level", {"levels": 3, "branching": 4}),
        ],
    ))
    if not smoke:
        # n=4096: same leaf count, 64-node leaves — resident state scales
        # with n_leaf, the broker tables do not
        scenarios.append(run_scenario(
            3, 4, 64, rounds=36, warmup=12, base_rate=12.0,
            plane_cfgs=[
                ("flat", {}),
                ("2-level", {"levels": 2, "branching": 8}),
                ("3-level", {"levels": 3, "branching": 4}),
            ],
        ))
    wallclock = time.perf_counter() - t0

    def plane(sc, name):
        return next(p for p in sc["planes"] if p["plane"] == name)

    big = [sc for sc in scenarios if sc["n"] >= 1000]
    report = {
        "bench": "trace_replay",
        "smoke": smoke,
        "wallclock_s": round(wallclock, 2),
        "scenarios": scenarios,
        "criterion": {
            # ISSUE gate 1: at n >= 1000 the 2-level plane's largest
            # resident component is STRICTLY below the flat plane's
            "hier_state_strictly_smaller": all(
                plane(sc, "2-level")["max_component_state"]
                < plane(sc, "flat")["max_component_state"]
                for sc in big
            ),
            # ISSUE gate 2: steady-state admission within 5 points of flat
            "admission_within_5pts": all(
                abs(plane(sc, name)["admission_rate"]
                    - plane(sc, "flat")["admission_rate"]) <= 0.05
                for sc in big for name in ("2-level", "3-level")
            ),
            # every plane's ledger balanced after churn + replay
            "conservation_ok": all(
                p["conservation_ok"] for sc in scenarios
                for p in sc["planes"]
            ),
            # no plane ever solved over more than a leaf-sized slice
            "solves_leaf_local": all(
                p["max_solve_n"] <= sc["k"] for sc in scenarios
                for p in sc["planes"]
            ),
            # CI slow-lane budget (smoke runs only)
            "within_wallclock_budget": (
                wallclock <= SMOKE_WALLCLOCK_BUDGET_S or not smoke
            ),
        },
    }
    report["ok"] = all(report["criterion"].values())
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    return report


def run_scale10k(out_path: str = "BENCH_trace10k.json", *,
                 rounds: int = 24, warmup: int = 8,
                 base_rate: float = 12.0) -> dict:
    """The ROADMAP's full 10k-node scheduled-lane point: one trace over
    ``region_tree(4, 4, 40)`` (256 40-node leaves, n=10240), replayed on
    the flat R=256 plane and the 2-level (16x16) hierarchy, each with the
    incremental fast path on and off.  Fewer rounds than the 1k/4k
    scenarios — at this scale each round already spans hundreds of
    region-local solves, and the point of the run is the scaling shape
    (resident state, admission, cache traffic), not tail quantiles."""
    t0 = time.perf_counter()
    sc = run_scenario(
        4, 4, 40, rounds=rounds, warmup=warmup, base_rate=base_rate,
        plane_cfgs=[
            ("flat", {}),
            ("flat-nocache", {"cache_enabled": False}),
            ("2-level", {"levels": 2, "branching": 16}),
            ("2-level-nocache",
             {"levels": 2, "branching": 16, "cache_enabled": False}),
        ],
    )
    wallclock = time.perf_counter() - t0

    def plane(name):
        return next(p for p in sc["planes"] if p["plane"] == name)

    report = {
        "bench": "trace_replay_10k",
        "wallclock_s": round(wallclock, 2),
        "scenario": sc,
        "criterion": {
            # the hierarchy's scaling claim holds at the full 10k point
            "hier_state_strictly_smaller":
                plane("2-level")["max_component_state"]
                < plane("flat")["max_component_state"],
            # the fast path pays for itself in traffic without costing
            # admitted work, at both plane shapes
            "cache_hits_positive": all(
                plane(name)["cache_hits"] > 0
                for name in ("flat", "2-level")
            ),
            "cache_admission_within_5pts": all(
                abs(plane(name)["admission_rate"]
                    - plane(f"{name}-nocache")["admission_rate"]) <= 0.05
                for name in ("flat", "2-level")
            ),
            "conservation_ok": all(
                p["conservation_ok"] for p in sc["planes"]),
            "solves_leaf_local": all(
                p["max_solve_n"] <= sc["k"] for p in sc["planes"]),
        },
    }
    report["ok"] = all(report["criterion"].values())
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    return report


def run_trace_export(out_path: str = "BENCH_trace_events.json",
                     *, seed: int = 9) -> dict:
    """Export a Perfetto/Chrome-trace JSON of one spanning request's full
    lifecycle over a line-of-regions plane: submit -> chained 2PC reserves
    across >= 2 regions -> commit -> release, plus the gossip rounds and
    per-region solve spans around it.  The exported file loads in
    ui.perfetto.dev / chrome://tracing; the acceptance check here is that
    the flow events reconstruct the lifecycle in order."""
    rng = np.random.default_rng(seed)
    R, k = 3, 4
    rg, assign = region_line(R, k, seed=seed)
    tracer = Tracer()
    cp = ControlPlane(
        rg, region_of=assign, method="leastcost_python", seed=seed,
        micro_batch=8, fanout=2, tracer=tracer,
    )
    cp.register_tenant("svc-a", weight=1.0)

    def mkdf(r1, r2, p):
        src = int(rng.choice(np.nonzero(assign == r1)[0]))
        dst = int(rng.choice(np.nonzero(assign == r2)[0]))
        creq = rng.uniform(0.02, 0.15, p).astype(np.float32)
        creq[0] = creq[-1] = 0.0
        breq = rng.uniform(0.5, 2.0, p - 1).astype(np.float32)
        return DataflowPath(creq, breq, src, dst)

    # background in-region traffic so the trace shows regional solve spans
    bg = [cp.submit("svc-a", mkdf(r, r, 3), klass=0) for r in range(R)]
    # THE spanning request: endpoints 2 regions apart -> chain r0-r1-r2
    rid = cp.submit("svc-a", mkdf(0, R - 1, 5), klass=1)
    for _ in range(6):
        cp.pump(rounds=1)
        if rid in cp.active_ids():
            break
    admitted = rid in cp.active_ids()
    if admitted:
        cp.release(rid)
    for b in bg:
        if b in cp.active_ids():
            cp.release(b)
    cp.check_invariants()

    doc = write_chrome_trace(tracer, out_path)
    errors = validate_chrome_trace(doc)
    life = reconstruct_request(doc, rid)
    names = [e["name"] for e in life]
    reserves = {e["args"]["region"] for e in life
                if e["name"] == "2pc.reserve" and "args" in e}
    lifecycle_ok = (
        admitted
        and names[:1] == ["submit"]
        and len(reserves) >= 2
        and "2pc.commit" in names
        and names[-1] == "release"
    )
    report = {
        "bench": "trace_export",
        "out": out_path,
        "events": len(doc["traceEvents"]),
        "spanning_rid": rid,
        "lifecycle": names,
        "regions_reserved": sorted(reserves),
        "criterion": {
            "schema_valid": not errors,
            "spanning_lifecycle_reconstructable": lifecycle_ok,
        },
        "schema_errors": errors[:8],
        "timeline": text_timeline(tracer, max_rows=12),
    }
    report["ok"] = all(report["criterion"].values())
    return report


def run(smoke: bool = True):
    """benchmarks.run harness hook: one CSV row per plane per scenario."""
    rep = run_json(smoke=smoke, out_path="BENCH_trace.json")
    rows = []
    for sc in rep["scenarios"]:
        for p in sc["planes"]:
            rows.append({
                "name": f"trace_n{sc['n']}_{p['plane']}",
                "us_per_call": 1e6 * p["wallclock_s"] / max(sc["rounds"], 1),
                "derived": (
                    f"admit={p['admission_rate']};"
                    f"p99_rounds={p['p99_admit_rounds']};"
                    f"state={p['max_component_state']};"
                    f"msgs_per_round={p['messages_per_round']}"
                ),
            })
    return rows


if __name__ == "__main__":
    import argparse

    from repro.core.device import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="n=1024 only; CI slow-lane budget")
    ap.add_argument("--out", default="BENCH_trace.json")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export a Perfetto/Chrome-trace JSON of one "
                         "spanning request's lifecycle and exit (skips "
                         "the replay benchmark)")
    ap.add_argument("--scale10k", action="store_true",
                    help="the scheduled-lane n=10240 point (flat vs "
                         "2-level, cache on/off) -> BENCH_trace10k.json; "
                         "skips the regular replay benchmark")
    args = ap.parse_args()
    if args.scale10k:
        rep = run_scale10k()
        sc = rep["scenario"]
        for p in sc["planes"]:
            print(f"n={sc['n']:5d} {p['plane']:16s} "
                  f"admit={p['admission_rate']:.3f} "
                  f"state={p['max_component_state']} "
                  f"hits={p['cache_hits']} warm={p['warm_solves']} "
                  f"wall={p['wallclock_s']}s")
        print(json.dumps(rep["criterion"], indent=2))
        print(f"ok={rep['ok']} wallclock={rep['wallclock_s']}s "
              "-> BENCH_trace10k.json")
        raise SystemExit(0 if rep["ok"] else 1)
    if args.trace_out is not None:
        rep = run_trace_export(args.trace_out)
        print(rep["timeline"])
        print(f"lifecycle: {' -> '.join(rep['lifecycle'])}")
        print(f"regions reserved: {rep['regions_reserved']}")
        print(json.dumps(rep["criterion"], indent=2))
        print(f"{rep['events']} events -> {args.trace_out} "
              "(load in ui.perfetto.dev)")
        raise SystemExit(0 if rep["ok"] else 1)
    rep = run_json(smoke=args.smoke, out_path=args.out)
    for sc in rep["scenarios"]:
        for p in sc["planes"]:
            print(f"n={sc['n']:5d} {p['plane']:8s} "
                  f"admit={p['admission_rate']:.3f} "
                  f"p99={p['p99_admit_rounds']:.1f} "
                  f"state={p['max_component_state']} "
                  f"msgs/round={p['messages_per_round']} "
                  f"wall={p['wallclock_s']}s")
    print(json.dumps(rep["criterion"], indent=2))
    print(f"ok={rep['ok']} wallclock={rep['wallclock_s']}s -> {args.out}")
    raise SystemExit(0 if rep["ok"] else 1)
