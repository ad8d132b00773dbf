"""Paper §3.4.1/§3.4.3, distributed claims: the distributed LeastCostMap is
optimal in >99% of cases with ~100x fewer messages than exhaustive flooding;
RandomNeighbor(k=1) reduces messages dramatically but loses quality.

Event-driven simulator (core/simulator.py) on Waxman topologies; plus the
BSP shard_map engine's async-equivalent message count for comparison.  All
solves go through the unified mapper engine (``repro.core.engine.solve``);
message counts come from the unified ``Stats``.

:func:`run_regional` extends the message story to the *control plane*
(``repro.service.regions``): it sweeps the regional plane over (R, fanout)
on a tenant-skewed overload workload, recording weighted fair-share
deviation, admission quality, per-round coordination messages (gossip +
2PC), gossip staleness, and the **compacted solve size** (mean padded n
per regional DP solve — n_r under the view substrate vs the global n the
masked plane paid) against the centralized PR-3 plane.
:func:`run_multi_hop` adds the multi-hop admission row: a line of regions
where every request spans >= 3 regions, admitted via chained 2PC
(previously dropped outright).  ``python -m benchmarks.bench_messages
--smoke`` writes the sweep + acceptance criteria to
``BENCH_messages.json`` (CI artifact).
"""
from __future__ import annotations

import json
import time

import numpy as np

from repro.core import (
    DataflowPath, SimConfig, pathmap_exact, random_dataflow, solve, waxman,
)


def run(n_instances: int = 25, n: int = 20, p: int = 6, seed0: int = 100,
        sizes=(20, 26)):
    # the reduction factor grows with n (paper: ~100x); n is capped by where
    # exhaustive flooding still terminates under the message budget
    rows = []
    for nn in sizes:
        rows += _run_one(n_instances, nn, p if nn <= 22 else 5, seed0)
    return rows


def _run_one(n_instances, n, p, seed0):
    policies = [
        ("exact", SimConfig(policy="exact", max_messages=3_000_000)),
        ("leastcost", SimConfig(policy="leastcost")),
        ("annealed", SimConfig(policy="annealed")),
        ("random_k1", SimConfig(policy="random_k", k=1)),
        ("random_k2", SimConfig(policy="random_k", k=2)),
        ("random_k3", SimConfig(policy="random_k", k=3)),
    ]
    stats = {name: {"msgs": [], "opt": 0, "found": 0, "t": 0.0} for name, _ in policies}
    bsp_msgs = []
    feas = 0
    for i in range(n_instances):
        rg = waxman(n, seed=seed0 + i)
        df = random_dataflow(rg, p, seed=seed0 + 5_000 + i)
        try:
            ex, _ = pathmap_exact(rg, df, max_states=400_000)
        except MemoryError:
            continue
        if ex is None:
            continue
        feas += 1
        for name, cfg in policies:
            t0 = time.perf_counter()
            try:
                m, st = solve(rg, df, method="simulate", cfg=cfg)
            except MemoryError:
                continue
            stats[name]["t"] += time.perf_counter() - t0
            stats[name]["msgs"].append(st.messages_sent)
            if m is not None:
                stats[name]["found"] += 1
                if abs(m.cost - ex.cost) < 1e-4:
                    stats[name]["opt"] += 1
        _, dst = solve(rg, df, method="shard_map")
        bsp_msgs.append(dst.messages_sent)

    rows = []
    base = np.mean(stats["exact"]["msgs"]) if stats["exact"]["msgs"] else float("nan")
    for name, _ in policies:
        s = stats[name]
        if not s["msgs"]:
            continue
        rows.append({
            "name": f"messages_{name}_n{n}",
            "us_per_call": 1e6 * s["t"] / max(feas, 1),
            "derived": (
                f"msgs_mean={np.mean(s['msgs']):.0f};"
                f"reduction_vs_exact={base/np.mean(s['msgs']):.1f}x;"
                f"optimal_rate={s['opt']/feas:.3f};found_rate={s['found']/feas:.3f}"
            ),
        })
    rows.append({
        "name": f"messages_bsp_shardmap_n{n}",
        "us_per_call": 0.0,
        "derived": (
            f"msgs_mean={np.mean(bsp_msgs):.0f};"
            f"reduction_vs_exact={base/np.mean(bsp_msgs):.1f}x"
        ),
    })
    return rows


# ---------------------------------------------------------------------------
# regional control plane: coordination messages vs fairness/admission
# ---------------------------------------------------------------------------


def _skewed_workload(rg, assign, n_per_tenant, p, seed):
    """Per-tenant request lists on one fixed partition: ``gold`` (weight 3)
    spreads uniformly over the whole network, ``bronze`` (weight 1) is
    concentrated in region 0 — the case where *local* per-region fairness
    is blind (each region only ever sees part of gold's global holdings)
    and gossiped estimates have to carry the signal."""
    rng = np.random.default_rng(seed)
    region0 = np.nonzero(assign == 0)[0]
    reqs = {"gold": [], "bronze": []}

    def _df(nodes):
        src, dst = rng.choice(nodes, size=2, replace=False)
        creq = rng.uniform(0.05, 0.25, size=p).astype(np.float32)
        creq[0] = creq[-1] = 0.0
        breq = rng.uniform(0.5, 2.0, size=p - 1).astype(np.float32)
        return DataflowPath(creq, breq, int(src), int(dst))

    for _ in range(n_per_tenant):
        reqs["gold"].append(_df(np.arange(rg.n)))
        reqs["bronze"].append(_df(region0))
    return reqs


def _solve_size(cp) -> dict:
    """Mean padded node dimension per DP solve: the regional plane reads
    its compacted substrate report; the centralized plane always solves
    at the global n."""
    if hasattr(cp, "solve_size_report"):
        rep = cp.solve_size_report()
        return {
            "global_n": rep["global_n"],
            "mean_solve_n": rep["mean_solve_n"],
            "max_solve_n": rep["max_solve_n"],
            "balanced_n_r": rep["balanced_n_r"],
        }
    st = cp.placer.stats
    return {
        "global_n": cp.placer.base.n,
        "mean_solve_n": st.mean_solve_n,
        "max_solve_n": cp.placer.base.n if st.solves else 0,
        "balanced_n_r": cp.placer.base.n,
    }


def _drive_plane(cp, reqs, pumps):
    for i in range(max(len(reqs["gold"]), len(reqs["bronze"]))):
        for t in ("gold", "bronze"):
            if i < len(reqs[t]):
                cp.submit(t, reqs[t][i])
    for _ in range(pumps):
        cp.pump()
    cp.check_invariants()
    held = cp.committed_capacity()
    total = sum(held.values()) or 1.0
    frac = {"gold": 0.75, "bronze": 0.25}  # weights 3:1, both saturated
    dev = {
        t: abs(held[t] / total - frac[t]) / frac[t] for t in held
    }
    led = cp.conservation()
    return {
        "committed": {t: float(v) for t, v in held.items()},
        "actual_fractions": {t: float(held[t] / total) for t in held},
        "target_fractions": frac,
        "deviation": {t: float(d) for t, d in dev.items()},
        "max_deviation": float(max(dev.values())),
        "admitted_fraction": led["active"] / max(led["submitted"], 1),
        "ledger": led,
        "solve_size": _solve_size(cp),
    }


def run_multi_hop(
    R: int = 6,
    k: int = 4,
    n_requests: int = 40,
    pumps: int = 8,
    seed: int = 9,
    method: str = "leastcost_python",
):
    """Multi-hop admission on a line of R fully-connected regions.

    Every request pins its endpoints at least two regions apart, so
    nothing is placeable without a spanning chain of >= 3 regions —
    exactly the workload the single-cut broker dropped outright.
    Records the admission fraction, the chain-length distribution proxy
    (max chain, multi-hop count) and the compacted solve sizes.
    """
    from repro.core import region_line
    from repro.service import FairSharePolicy, RegionalControlPlane

    rg, assign = region_line(R, k, seed=seed)
    cp = RegionalControlPlane(
        rg, regions=R, region_of=assign, fanout=2, seed=seed,
        micro_batch=16, policy=FairSharePolicy(slack=0.4), method=method,
    )
    cp.register_tenant("gold", weight=3.0)
    cp.register_tenant("bronze", weight=1.0)
    rng = np.random.default_rng(seed)
    for i in range(n_requests):
        tenant = "gold" if i % 2 == 0 else "bronze"
        r1 = int(rng.integers(0, R - 2))
        r2 = int(rng.integers(r1 + 2, R))  # >= 2 regions apart: chain >= 3
        src = int(rng.choice(np.nonzero(assign == r1)[0]))
        dst = int(rng.choice(np.nonzero(assign == r2)[0]))
        p = int(rng.integers(2, 6))
        creq = rng.uniform(0.02, 0.15, p).astype(np.float32)
        creq[0] = creq[-1] = 0.0
        breq = rng.uniform(0.5, 2.0, p - 1).astype(np.float32)
        cp.submit(tenant, DataflowPath(creq, breq, src, dst))
    for _ in range(pumps):
        cp.pump()
    cp.check_invariants()
    led = cp.conservation()
    return {
        "R": R, "k": k, "n": rg.n, "requests": n_requests, "pumps": pumps,
        "admitted_fraction": led["active"] / max(led["submitted"], 1),
        "ledger": led,
        "spanning": dict(cp.span_stats),
        "twopc_messages": cp.engine_stats().twopc_messages,
        "solve_size": _solve_size(cp),
        "gossip_window": cp.bus.snapshot(reset=True),
    }


def run_multi_hop_hotspot(
    rows: int = 2,
    cols: int = 3,
    k: int = 3,
    n_requests: int = 24,
    pumps: int = 6,
    chain_k: int = 2,
    seed: int = 9,
    method: str = "leastcost_python",
):
    """Gateway-hotspot scenario on a region grid: a standing reservation
    saturates the (0, 1) cut, then every request pins src in region 0 /
    dst in region 2 — the fewest-hop chain 0-1-2 runs through the hot
    cut, but the grid has cold bypass chains around it.

    Three planes serve the identical workload:

    - ``uniform``: chain_k racer on the *cold* grid — the reference
      admission rate with no hotspot;
    - ``hot_single``: chain_k=1 on the hot grid — the legacy broker
      burns every attempt on the one saturated chain (collapse);
    - ``hot_k``: the chain_k racer on the hot grid — must route around
      the hotspot and recover the uniform admission rate, inside the
      single-chain 2PC candidate budget.
    """
    from repro.core import region_grid
    from repro.service import FairSharePolicy, RegionalControlPlane

    def _drive(ck, hot):
        rg, assign = region_grid(rows, cols, k, seed=seed)
        cp = RegionalControlPlane(
            rg, regions=rows * cols, region_of=assign, fanout=2,
            seed=seed, micro_batch=16, chain_k=ck,
            policy=FairSharePolicy(slack=0.4), method=method,
        )
        cp.register_tenant("gold", weight=3.0)
        cp.register_tenant("bronze", weight=1.0)
        if hot:
            (e,) = cp._cut_by_pair[(0, 1)]
            u, v = e
            b = cp.cut_residual[e] - 0.25  # leave less than any breq below
            cp.submit("bronze", DataflowPath.make([0.01, 0.01], [b], u, v))
            cp.pump()
            assert cp.cut_residual[e] < 0.3, "hotspot setup failed"
        base = cp.conservation()["active"]
        rng = np.random.default_rng(seed + 1)
        for i in range(n_requests):
            tenant = "gold" if i % 2 == 0 else "bronze"
            src = int(rng.choice(np.nonzero(assign == 0)[0]))
            dst = int(rng.choice(np.nonzero(assign == 2)[0]))
            p = int(rng.integers(3, 6))
            creq = rng.uniform(0.02, 0.12, p).astype(np.float32)
            creq[0] = creq[-1] = 0.0
            breq = rng.uniform(0.4, 1.0, p - 1).astype(np.float32)
            cp.submit(tenant, DataflowPath(creq, breq, src, dst))
        for _ in range(pumps):
            cp.pump()
        cp.check_invariants()
        led = cp.conservation()
        return {
            "chain_k": ck, "hotspot": hot,
            "admitted_fraction": (led["active"] - base) / n_requests,
            "ledger": led,
            "spanning": dict(cp.span_stats),
            "twopc_messages": cp.engine_stats().twopc_messages,
            "max_cut_attempts": cp.max_cut_attempts,
        }

    uniform = _drive(chain_k, hot=False)
    hot_single = _drive(1, hot=True)
    hot_k = _drive(chain_k, hot=True)
    # racing never widens the probe budget: the per-candidate message
    # bound is the SAME max_cut_attempts quota the single-chain broker
    # had (<= chain_k x that quota by construction, 1x in fact)
    max_chain = max(hot_k["spanning"]["max_chain"], 2)
    budget_ok = hot_k["twopc_messages"] <= (
        hot_k["spanning"]["attempts"] * chain_k
        * hot_k["max_cut_attempts"] * (2 * max_chain + 2)
    )
    return {
        "rows": rows, "cols": cols, "k": k, "chain_k": chain_k,
        "requests": n_requests, "pumps": pumps,
        "uniform": uniform,
        "hot_single_chain": hot_single,
        "hot_k_chain": hot_k,
        "hotspot_admitted_gap": abs(
            hot_k["admitted_fraction"] - uniform["admitted_fraction"]),
        "message_budget_bounded": bool(budget_ok),
    }


def run_regional(
    n: int = 24,
    p: int = 4,
    n_per_tenant: int = 60,
    pumps: int = 10,
    sweep=((1, 2), (2, 2), (4, 0), (4, 1), (4, 2)),
    R_max: int = 4,
    seed: int = 7,
    method: str = "leastcost_python",
    out_path: str | None = "BENCH_messages.json",
):
    """Regional-plane sweep over (R, fanout) vs the centralized plane.

    Both planes serve the identical tenant-skewed overload workload
    (weights 3:1).  Recorded per point: weighted fair-share deviation of
    the standing allocation, admitted fraction, coordination messages per
    pump round (gossip exactly ``R * fanout`` + bounded 2PC) and gossip
    staleness.  Criteria (the PR acceptance gates):

    - at R=4 with the default fanout the weighted fair-share deviation
      stays within 15 percentage-of-target points of the centralized
      plane's;
    - per-round gossip messages are exactly ``R * fanout`` — O(R*fanout),
      not O(n^2);
    - every regional solve runs over the compacted substrate: mean/max
      padded solve dimension <= ceil(n/R) + slack, never the global n;
    - dataflows spanning >= 3 regions are admitted via multi-hop 2PC
      (``run_multi_hop``; admission rate > 0 where the single-cut broker
      dropped them);
    - R=1 bit-identity with the centralized plane is enforced separately
      in ``tests/test_regions.py`` (noted here for the record).
    """
    from repro.service import (
        ControlPlane, FairSharePolicy, RegionalControlPlane,
        partition_regions,
    )

    rg = waxman(n, seed=seed)
    assign = partition_regions(rg, R_max, seed=seed)
    reqs = _skewed_workload(rg, assign, n_per_tenant, p, seed)
    kw = dict(policy=FairSharePolicy(slack=0.4), micro_batch=16,
              method=method)

    def _fresh(regions=None, fanout=None):
        if regions is None:
            return ControlPlane(rg, **kw)
        # regional machinery even at R=1 (the facade would degrade it to
        # the centralized plane — here the degenerate case is the point)
        return RegionalControlPlane(rg, regions=regions, fanout=fanout,
                                    seed=seed, **kw)

    def _register(cp):
        cp.register_tenant("gold", weight=3.0)
        cp.register_tenant("bronze", weight=1.0)
        return cp

    central = _drive_plane(_register(_fresh()), reqs, pumps)
    points = []
    for (R, fanout) in sweep:
        cp = _register(_fresh(R, fanout))
        rec = _drive_plane(cp, reqs, pumps)
        rec.update({
            "R": R, "fanout": fanout,
            "coordination": cp.coordination_report(),
            "gossip_messages_per_round": (
                cp.bus.messages_sent / max(cp.bus.rounds, 1)
            ),
            # windowed counters: this point's gossip volume only, however
            # the plane is driven afterwards (closes the window, never
            # rewinds the lifetime counters the gates above read)
            "gossip_window": cp.bus.snapshot(reset=True),
            # unified telemetry snapshot (per-region registries merged
            # under plane=r{r} labels + broker gossip/2PC/span counters)
            "telemetry": cp.metrics_registry().snapshot(),
        })
        points.append(rec)

    # the fairness gate grades the most decentralized point with the most
    # gossip: largest R, then largest fanout, in whatever sweep ran
    gate = max(points, key=lambda x: (x["R"], x["fanout"]))
    # solve-size gate: the compacted substrate must keep every regional
    # solve at n_r <= ceil(n/R) + slack, never the global n
    slack = 2
    size_ok = all(
        x["solve_size"]["mean_solve_n"]
        <= x["solve_size"]["balanced_n_r"] + slack
        and x["solve_size"]["max_solve_n"]
        <= x["solve_size"]["balanced_n_r"] + slack
        for x in points if x["R"] > 1
    )
    multi_hop = run_multi_hop(method=method)
    hotspot = run_multi_hop_hotspot(method=method)
    record = {
        "n": n, "p": p, "n_per_tenant": n_per_tenant, "pumps": pumps,
        "seed": seed, "method": method, "weights": {"gold": 3.0, "bronze": 1.0},
        "centralized": central,
        "sweep": points,
        "multi_hop": multi_hop,
        "multi_hop_hotspot": hotspot,
        "criterion": {
            "gate_point": {"R": gate["R"], "fanout": gate["fanout"]},
            "r4_fairness_within_15pct_of_centralized": bool(
                gate["max_deviation"] <= central["max_deviation"] + 0.15
            ),
            "r4_centralized_deviation": central["max_deviation"],
            "r4_regional_deviation": gate["max_deviation"],
            "gossip_messages_O_R_fanout": all(
                x["coordination"]["gossip_messages"]
                == pumps * x["R"] * min(x["fanout"], x["R"] - 1)
                for x in points
            ),
            # payload accounting: every gossip message carries at most R
            # records (a region pushes its whole view, never more), so the
            # per-round record volume is O(R * fanout) records — bandwidth
            # scales with the region count, not with node count or time
            "gossip_payload_O_R_fanout_records": all(
                x["coordination"]["gossip"]["records_per_message"] <= x["R"]
                and x["coordination"]["gossip"]["records_per_round"]
                <= x["R"] * min(x["fanout"], x["R"] - 1) * x["R"]
                for x in points if x["R"] > 1
            ),
            "compacted_solve_n_le_balanced": bool(size_ok),
            "solve_n_slack": slack,
            "solve_size_reduction_at_gate": (
                float(n) / max(gate["solve_size"]["mean_solve_n"], 1e-9)
            ),
            "multi_hop_admitted": bool(
                multi_hop["admitted_fraction"] > 0
                and multi_hop["spanning"]["max_chain"] >= 3
            ),
            "multi_hop_admitted_fraction": multi_hop["admitted_fraction"],
            # gateway-hotspot gates: the k-chain racer recovers the
            # uniform-load admission rate (within 0.1) where the legacy
            # single-chain broker collapses, without widening the 2PC
            # candidate budget past k x the single-chain quota
            "multi_hop_hotspot_admitted": bool(
                hotspot["hotspot_admitted_gap"] <= 0.1
                and hotspot["hot_single_chain"]["admitted_fraction"]
                <= hotspot["uniform"]["admitted_fraction"] - 0.3
                and hotspot["hot_k_chain"]["spanning"]["rerouted"] >= 1
            ),
            "hotspot_uniform_fraction": (
                hotspot["uniform"]["admitted_fraction"]),
            "hotspot_single_chain_fraction": (
                hotspot["hot_single_chain"]["admitted_fraction"]),
            "hotspot_k_chain_fraction": (
                hotspot["hot_k_chain"]["admitted_fraction"]),
            "hotspot_message_budget_bounded": (
                hotspot["message_budget_bounded"]),
            "r1_bit_identity": "enforced in tests/test_regions.py",
            "k1_bit_identity": "enforced in tests/test_regions.py",
        },
    }
    if out_path is not None:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
    return record


if __name__ == "__main__":
    import argparse

    from repro.core.device import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="regional sweep only, CI sizes; writes "
                         "BENCH_messages.json")
    args = ap.parse_args()
    if args.smoke:
        rec = run_regional()
    else:
        for row in run():
            print(f"{row['name']},{row['us_per_call']:.1f},\"{row['derived']}\"")
        rec = run_regional()
    print(json.dumps(
        {"regional": {k: rec[k] for k in ("centralized", "criterion")},
         "multi_hop": rec["multi_hop"],
         "multi_hop_hotspot": {
             k: rec["multi_hop_hotspot"][k]
             for k in ("hotspot_admitted_gap", "message_budget_bounded")
         },
         "sweep": [
             {"solve_n": x["solve_size"]["mean_solve_n"],
              **{k: x[k] for k in ("R", "fanout", "max_deviation",
                                   "admitted_fraction",
                                   "gossip_messages_per_round")}}
             for x in rec["sweep"]
         ]}, indent=2))
