"""Placement-engine benchmark.

1. BCPM planning for every assigned architecture on the 2-pod slice graph
   (quality = end-to-end route latency; time = solver wall clock, warm jit).
2. Online multi-request placement service (``core.online.OnlinePlacer``):
   batched-kernel vs vmapped-jnp vs sequential ``solve()`` on the same
   request stream, plus a speedup curve over batch size and network size
   and an admission + churn exercise with residual-capacity invariants.
3. Streaming admission under a Poisson arrival/departure process with
   periodic node churn (the paper's dynamic scenario, quantified):
   steady-state admission rate and re-map latency, plus an offered-load
   sweep (rate x hold) past the knee of the admission-rate curve, and a
   pipeline-depth column at the knee (async pipelined admission: device
   solves overlapped with host commits; gated in ``criterion``).
4. Multi-tenant fairness at the knee (``repro.service.ControlPlane``):
   two tenants, weights 3:1, identical offered overload — weighted
   max-min standing shares vs the FCFS baseline — ending with the
   background-defrag pass on the churn-fragmented network.

``python -m benchmarks.bench_placement [--smoke]`` writes the online-service
numbers to ``BENCH_placement.json``, the churn process + overload sweep to
``BENCH_streaming.json`` and the fairness/defrag scenario to
``BENCH_fairness.json`` (all CI artifacts).

Off-TPU the ``use_kernel=True`` path runs the fused batched jnp mirror of
the Pallas superstep kernel (``kernels/minplus/batched``) — same math, same
shared-network batching, no per-request vmap graph.  On TPU the Pallas
kernel replaces it; its expected advantage is the HBM-traffic model in the
kernel's module docstring (O(n^2 + B*n*K) vs O(B*n^2*K) per superstep).
"""
from __future__ import annotations

import heapq
import json
import time

import numpy as np

from repro.core import (
    AdmissionPipeline,
    OnlinePlacer,
    random_dataflow,
    solve,
    solve_batch,
    waxman,
)


def run_archs():
    from repro.configs import ARCHS, get_config
    from repro.launch.placement import PodTopology, plan_pipeline
    from repro.models.config import SHAPES

    rows = []
    topo = PodTopology(pods=2)
    for arch in ARCHS:
        cfg = get_config(arch)
        plan_pipeline(cfg, SHAPES["train_4k"], topo, steps_per_sec=0.05,
                      dst_slice=topo.n_slices - 1)  # warm
        t0 = time.perf_counter()
        plan = plan_pipeline(cfg, SHAPES["train_4k"], topo, steps_per_sec=0.05,
                             dst_slice=topo.n_slices - 1)
        dt = time.perf_counter() - t0
        rows.append({
            "name": f"placement_{arch}",
            "us_per_call": 1e6 * dt,
            "derived": (
                f"stages={len(plan.stage_slices)};latency_us={plan.latency_us:.1f};"
                f"route_len={len(plan.route)}" if plan else "infeasible"
            ),
        })
    return rows


def _request_stream(rg, n_requests: int, p: int, seed0: int):
    """Light concurrent requests: many fit the shared network at once."""
    return [
        random_dataflow(rg, p, seed=seed0 + i,
                        creq_range=(0.02, 0.15), breq_range=(0.5, 4.0))
        for i in range(n_requests)
    ]


def _best_time(fn, reps: int = 7) -> float:
    """min-of-reps wall clock: the robust statistic on noisy shared runners
    (the true cost is the floor; everything above it is interference)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run_batch_curve(*, n_list=(16, 32), batch_list=(1, 8, 32, 64), p: int = 6,
                    seed: int = 3, reps: int = 20):
    """Speedup curve over batch size and network size: batched-kernel DP vs
    vmapped-jnp DP vs a sequential solve loop on one shared network.

    The DP is timed directly (jit + block_until_ready): parent-pointer
    reconstruction is identical python work on both batched paths and would
    only add noise to the comparison the kernel changes.
    """
    import jax

    from repro.core.leastcost import _leastcost_dp_batched, _vmapped_dp
    from repro.core.problem import stack_requests

    curve = []
    for n in n_list:
        rg = waxman(n, seed=seed)
        dfs_all = _request_stream(rg, max(batch_list), p, seed0=2000)
        solve(rg, dfs_all[0], method="leastcost_jax")  # warm single shape
        for b in batch_list:
            dfs = dfs_all[:b]
            tensors, p_max = stack_requests(rg, dfs)
            vmapped = _vmapped_dp(n, p_max, n - 1)  # same cached jit as prod
            f_v = lambda: jax.block_until_ready(vmapped(tensors)[0])  # noqa: E731
            f_k = lambda: jax.block_until_ready(  # noqa: E731
                _leastcost_dp_batched(tensors, B=b, n=n, p=p_max,
                                      max_rounds=n - 1, impl="ref")[0])
            f_v(), f_k()  # warm both compiled paths
            # each path is measured in steady state (warm, back-to-back,
            # min-of-reps): alternating executables every call adds
            # allocator/cache churn that swamps the ~10% DP difference
            t_vmap = _best_time(f_v, reps)
            t_kern = _best_time(f_k, reps)
            t_seq = _best_time(
                lambda: [solve(rg, df, method="leastcost_jax") for df in dfs],
                max(2, reps - 4))
            curve.append({
                "n": n, "batch": b, "kernel_impl": "ref",
                "sequential_solve_s": t_seq, "vmapped_dp_s": t_vmap,
                "kernel_dp_s": t_kern,
                "kernel_vs_vmapped": t_vmap / max(t_kern, 1e-9),
            })
    return curve


def run_online(*, n: int = 24, p: int = 6, n_requests: int = 128,
               micro_batch: int = 64, seed: int = 7,
               curve_kwargs: dict | None = None,
               out_path: str = "BENCH_placement.json"):
    rg = waxman(n, seed=seed)
    dfs = _request_stream(rg, n_requests, p, seed0=1000)

    # DP speedup curve first: measured in a quiet process, before the
    # service exercise below fills the jit cache and allocator
    curve = run_batch_curve(**(curve_kwargs or {}))

    # warm all jit paths (single-request, batched, batched-kernel shapes)
    solve(rg, dfs[0], method="leastcost_jax")
    solve_batch(rg, dfs[:micro_batch], method="leastcost_jax")
    solve_batch(rg, dfs[:micro_batch], method="leastcost_jax", use_kernel=True)

    seq = [solve(rg, df, method="leastcost_jax")[0] for df in dfs]
    t_seq = _best_time(
        lambda: [solve(rg, df, method="leastcost_jax") for df in dfs], reps=3)

    def run_batched(**kw):
        out = []
        for i in range(0, n_requests, micro_batch):
            ms, _ = solve_batch(rg, dfs[i:i + micro_batch],
                                method="leastcost_jax", **kw)
            out.extend(ms)
        return out

    bat = run_batched()
    t_bat = _best_time(run_batched, reps=3)

    ker = run_batched(use_kernel=True)
    t_ker = _best_time(lambda: run_batched(use_kernel=True), reps=3)

    def _agree(a_list, b_list):
        return sum(
            (a is None) == (b is None)
            and (a is None or abs(a.cost - b.cost) < 1e-3)
            for a, b in zip(a_list, b_list)
        ) / n_requests

    # admission + churn against residual capacity (kernel path)
    placer = OnlinePlacer(rg, use_kernel=True)
    tickets = []
    for i in range(0, n_requests, micro_batch):
        tickets.extend(placer.admit_many(dfs[i:i + micro_batch]))
    placer.check_invariants()
    admitted_stream = placer.stats.admitted  # before churn re-admissions
    busiest = max(
        (v for t in tickets if t for v in t.mapping.route
         if v not in (t.df.src, t.df.dst)),
        key=lambda v: sum(v in t.mapping.route for t in tickets if t),
        default=0,
    )
    remapped, dropped = placer.fail_node(busiest)
    placer.check_invariants()

    record = {
        "n": n, "p": p, "n_requests": n_requests, "micro_batch": micro_batch,
        "sequential_s": t_seq, "batched_s": t_bat, "kernel_s": t_ker,
        "speedup": t_seq / max(t_bat, 1e-9),
        "speedup_kernel": t_seq / max(t_ker, 1e-9),
        "kernel_vs_vmapped": t_bat / max(t_ker, 1e-9),
        "agreement": _agree(seq, bat),
        "agreement_kernel": _agree(seq, ker),
        "admitted": admitted_stream,
        "admitted_total": placer.stats.admitted,  # incl. churn re-admissions
        "rejected": placer.stats.rejected,
        "batch_conflicts": placer.stats.batch_conflicts,
        "churn": {
            "failed_node": int(busiest),
            "displaced": len(remapped) + len(dropped),
            "remapped": len(remapped),
            "dropped": len(dropped),
        },
        "invariants_ok": True,
        "curve": curve,
        "tpu_note": (
            "off-TPU use_kernel runs the fused-jnp mirror of the batched "
            "Pallas superstep, which XLA compiles to nearly the same code "
            "as the jitted vmap — kernel_vs_vmapped ~1.0 +/- runner noise "
            "is the expected CPU reading.  The kernel's claimed advantage "
            "is the TPU HBM-traffic model (O(n^2 + B*n*K) vs O(B*n^2*K) "
            "per superstep, lat/bw tiles shared across the batch; see "
            "kernels/minplus/batched.py) which a CPU proxy cannot exhibit."
        ),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    return record


def _poisson_times(rng, rate: float, horizon: float) -> list[float]:
    ts, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / max(rate, 1e-9))
        if t >= horizon:
            break
        ts.append(t)
    return ts


def run_streaming(*, n: int = 24, p: int = 5, rate: float = 24.0,
                  hold: float = 2.0, horizon: float = 10.0, tick: float = 0.25,
                  fail_every: float = 2.5, warmup: float = 2.0, seed: int = 11,
                  use_kernel: bool = True, pipeline_depth: int = 1,
                  cache: bool = True, repeat_pool: int | None = None,
                  out_path: str | None = "BENCH_streaming.json"):
    """Poisson arrival/departure process against one shared network.

    Requests arrive at ``rate``/unit-time, hold capacity for Exp(``hold``)
    and depart; every ``fail_every`` units a busy node fails (displacing its
    tickets through re-admission) and the previously failed node restores.
    Virtual time drives the process; wall clock is measured only around the
    micro-batched admissions and the churn re-maps.  ``out_path=None`` skips
    the JSON write (used by the overload sweep).

    ``pipeline_depth`` routes the tick batches through an
    :class:`~repro.core.AdmissionPipeline`: at depth d, a tick's solve is
    dispatched immediately but commits only when the window forces it out
    (or at the end-of-horizon flush), so device DPs overlap the host-side
    validate/commit of earlier batches.  ``depth=1`` commits every push
    in-line and is bit-identical to the synchronous ``admit_many`` path.
    Admissions are attributed to the *dispatch* tick for rate accounting
    (offered vs admitted must pair up) and to the *commit* tick for the
    departure clock (capacity is only held once committed).

    ``steady_admission_rate`` counts only arrivals after ``warmup``: the
    ramp-up (an empty network admits everything) otherwise masks the
    saturation knee the overload sweep is looking for.

    ``cache`` toggles the placer's incremental fast path;
    ``repeat_pool=k`` makes the workload repeat-heavy — the arrival
    stream cycles through ``k`` distinct request shapes instead of
    drawing a fresh one per arrival, which is the regime the
    mapping-reuse cache is built for (``run_cache_fastpath`` pairs the
    two knobs into the gated on/off comparison).
    """
    rng = np.random.default_rng(seed)
    rg = waxman(n, seed=seed)
    placer = OnlinePlacer(rg, use_kernel=use_kernel, cache_enabled=cache)
    warm_max = placer.warmup(max_batch=int(max(4 * rate * tick, 2)), p=p)
    pipe = AdmissionPipeline(placer, depth=pipeline_depth)

    # Poisson arrivals over the horizon
    arrivals = _poisson_times(rng, rate, horizon)
    if repeat_pool:
        pool = _request_stream(rg, repeat_pool, p, seed0=int(seed) * 131)
        reqs = [pool[k % repeat_pool] for k in range(len(arrivals))]
    else:
        reqs = _request_stream(rg, len(arrivals), p, seed0=int(seed) * 131)

    departures: list[tuple[float, int]] = []  # heap of (t_depart, tid)
    admit_ms: list[float] = []
    admit_ms_steady: list[float] = []  # pushes after `warmup` only
    remap_ms: list[float] = []
    displaced_total = remapped_total = 0
    offered = admitted_arrivals = 0  # arrival stream only (churn re-
    # admissions are tracked separately via placer.stats)
    offered_steady = admitted_steady = 0  # arrivals after `warmup`
    occupancy: list[int] = []
    failed_node: int | None = None
    next_fail = fail_every
    i = 0
    now = 0.0
    while now < horizon:
        now = min(now + tick, horizon)
        # departures due by `now`
        while departures and departures[0][0] <= now:
            _, tid = heapq.heappop(departures)
            if tid in placer.tickets:
                placer.release(tid)
        # churn: restore the previous casualty, fail the busiest node
        if now >= next_fail:
            next_fail += fail_every
            if failed_node is not None:
                placer.restore_node(failed_node)
            load = np.zeros(n)
            for tk in placer.tickets.values():
                for v in tk.mapping.route:
                    if v not in (tk.df.src, tk.df.dst):
                        load[v] += 1
            if load.max() > 0:
                failed_node = int(load.argmax())
                t0 = time.perf_counter()
                rem, drop = placer.fail_node(failed_node)
                remap_ms.append(1e3 * (time.perf_counter() - t0))
                displaced_total += len(rem) + len(drop)
                remapped_total += len(rem)
                # re-mapped tickets keep their tid, so the originally
                # scheduled departure entries stay valid — nothing to re-push
        # micro-batch the tick's arrivals
        batch = []
        while i < len(arrivals) and arrivals[i] <= now:
            batch.append(reqs[i])
            i += 1
        if batch:
            offered += len(batch)
            if now >= warmup:
                offered_steady += len(batch)
            t0 = time.perf_counter()
            committed = pipe.push(batch, tag=(now >= warmup))
            dt_ms = 1e3 * (time.perf_counter() - t0)
            admit_ms.append(dt_ms)
            if now >= warmup:
                admit_ms_steady.append(dt_ms)
            for pending, tickets in committed:
                for tk in tickets:
                    if tk is not None:
                        admitted_arrivals += 1
                        if pending.tag:  # steady flag from dispatch time
                            admitted_steady += 1
                        heapq.heappush(
                            departures, (now + rng.exponential(hold), tk.tid))
        occupancy.append(len(placer.tickets))
    # end-of-stream barrier: commit whatever the window still holds.  Timed
    # separately — one flush drains up to depth-1 batches, which is a
    # shutdown cost, not a per-admission latency sample.
    flush_ms = 0.0
    if pipe.in_flight:
        t0 = time.perf_counter()
        tail = pipe.flush()
        flush_ms = 1e3 * (time.perf_counter() - t0)
        for pending, tickets in tail:
            for tk in tickets:
                if tk is not None:
                    admitted_arrivals += 1
                    if pending.tag:
                        admitted_steady += 1
    placer.check_invariants()

    st = placer.stats
    record = {
        "n": n, "p": p, "rate": rate, "hold": hold, "horizon": horizon,
        "tick": tick, "fail_every": fail_every, "use_kernel": use_kernel,
        "pipeline_depth": pipeline_depth,
        "warmed_buckets_to": warm_max,  # larger churn batches may compile
        "offered": offered,
        "admitted": admitted_arrivals,  # arrival stream only
        "admitted_total": st.admitted,  # incl. churn re-admissions
        "rejected_total": st.rejected,
        "admission_rate": admitted_arrivals / max(offered, 1),
        "warmup": warmup,
        "steady_admission_rate": admitted_steady / max(offered_steady, 1),
        "steady_state_occupancy": float(np.mean(occupancy)) if occupancy else 0,
        "batches": st.batches,
        "batch_conflicts": st.batch_conflicts,
        "admit_ms_mean": float(np.mean(admit_ms)) if admit_ms else 0.0,
        "admit_ms_p95": float(np.percentile(admit_ms, 95)) if admit_ms else 0.0,
        # ramp-up excluded, same convention as steady_admission_rate: the
        # first pushes pay the one-time pool-fill solves (and, cache-on,
        # the signature-cache cold misses), which are not the steady tail
        "admit_ms_p95_steady": float(np.percentile(admit_ms_steady, 95))
        if admit_ms_steady else 0.0,
        "admit_ms_mean_steady": float(np.mean(admit_ms_steady))
        if admit_ms_steady else 0.0,
        "churn_events": len(remap_ms),
        "displaced": displaced_total,
        "remapped": remapped_total,
        "dropped": st.dropped,
        "remap_ms_mean": float(np.mean(remap_ms)) if remap_ms else 0.0,
        "remap_ms_p95": float(np.percentile(remap_ms, 95)) if remap_ms else 0.0,
        "solve_ms_total": st.solve_ms,
        "overhead_ms_total": st.overhead_ms,
        "conflict_resolve_ms": st.conflict_resolve_ms,
        "stale_batches": st.stale_batches,
        "flush_ms": flush_ms,
        "cache_enabled": cache,
        "repeat_pool": repeat_pool,
        "solves": st.solves,
        "cache_hits": st.cache_hits,
        "cache_misses": st.cache_misses,
        "cache_stale": st.cache_stale,
        "cache_neg_hits": st.cache_neg_hits,
        "hit_rate": st.cache_hits / max(
            st.cache_hits + st.cache_misses + st.cache_stale
            + st.cache_neg_hits, 1),
        "warm_solves": st.warm_solves,
        "warm_fallbacks": st.warm_fallbacks,
        "supersteps": {m: dict(b) for m, b in st.supersteps.items()},
        "invariants_ok": True,
    }
    if out_path is not None:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
    return record


def run_overload_sweep(*, rates=(12.0, 24.0, 48.0, 96.0, 192.0),
                       n: int = 24, p: int = 5, hold: float = 4.0,
                       horizon: float = 6.0, warmup: float = 2.0,
                       knee_threshold: float = 0.9,
                       pipeline_depths=(1, 2, 4),
                       pipeline_reps: int = 2,
                       seed: int = 11, use_kernel: bool = True,
                       baseline_rate: float = 24.0,
                       baseline_hold: float = 2.0,
                       out_path: str | None = "BENCH_streaming.json"):
    """Sweep offered load (arrival rate x hold time) past the admission knee.

    The ROADMAP observation: at the original operating point the service
    admits >90% — the interesting regime (where fairness and defrag matter)
    starts where admission collapses.  Offered concurrency is
    ``rate x hold``, so the sweep fixes a longer ``hold`` and doubles the
    rate until *steady-state* admission (ramp-up excluded; see
    ``run_streaming(warmup=...)``) falls below ``knee_threshold``.  That
    first saturated point is recorded as the knee; the fairness benchmark
    (``run_fairness``) runs past it on the same network.

    The knee point is then re-run at each ``pipeline_depths`` entry — the
    regime where batches are large and the network is contended, i.e. where
    pipelining has both the most to gain (device DP overlapped with host
    commit) and the most to lose (stale optimistic solves re-solved one by
    one).  ``record["criterion"]`` gates the trade: the deepest pipeline's
    admit p95 must stay within 1.1x of the synchronous knee value, and its
    steady-state admission rate within 2 points of the synchronous path.
    """
    base = run_streaming(n=n, p=p, rate=baseline_rate, hold=baseline_hold,
                         horizon=horizon, warmup=warmup, seed=seed,
                         use_kernel=use_kernel, out_path=None)
    sweep = []
    for r in sorted(rates):
        rec = run_streaming(n=n, p=p, rate=float(r), hold=hold,
                            horizon=horizon, warmup=warmup, seed=seed,
                            use_kernel=use_kernel, out_path=None)
        sweep.append({
            "rate": float(r),
            "hold": hold,
            "offered_concurrency": float(r) * hold,
            "offered": rec["offered"],
            "admission_rate": rec["admission_rate"],
            "steady_admission_rate": rec["steady_admission_rate"],
            "occupancy": rec["steady_state_occupancy"],
            "admit_ms_mean": rec["admit_ms_mean"],
        })
    found = next(
        (s for s in sweep if s["steady_admission_rate"] < knee_threshold),
        None,
    )
    knee = found if found is not None else sweep[-1]

    # ---- pipeline-depth column at the knee ------------------------------
    # Virtual time makes admission outcomes deterministic per (depth, seed);
    # only the wall-clock columns vary between reps.  min-of-reps on the
    # p95 is the same robust-floor statistic ``_best_time`` uses: the true
    # admission cost is the floor, everything above it is runner
    # interference.  A longer horizon gives the percentile enough samples
    # (~64 pushes at 16s vs ~20 at the smoke horizon) that the p95 is a
    # deep quantile instead of the 2nd-worst sample: both depths' tails
    # are churn-push costs of ~equal magnitude, so with enough samples the
    # ratio concentrates near 1 and the 1.1x gate has real margin.
    pipeline = []
    for d in sorted({max(1, int(d)) for d in pipeline_depths}):
        best = None
        for _ in range(pipeline_reps):
            rec = run_streaming(n=n, p=p, rate=knee["rate"],
                                hold=knee["hold"],
                                horizon=max(horizon, 16.0), warmup=warmup,
                                seed=seed, use_kernel=use_kernel,
                                pipeline_depth=d, out_path=None)
            if best is None or rec["admit_ms_p95"] < best["admit_ms_p95"]:
                best = rec
        pipeline.append({
            "pipeline_depth": d,
            "admit_ms_mean": best["admit_ms_mean"],
            "admit_ms_p95": best["admit_ms_p95"],
            "steady_admission_rate": best["steady_admission_rate"],
            "batch_conflicts": best["batch_conflicts"],
            "stale_batches": best["stale_batches"],
            "conflict_resolve_ms": best["conflict_resolve_ms"],
            "overhead_ms_total": best["overhead_ms_total"],
        })
    d_sync, d_deep = pipeline[0], pipeline[-1]

    # ---- disabled-telemetry overhead gate --------------------------------
    # Every admission crosses a bounded number of instrumentation sites
    # (pump/solve/dispatch/commit spans + flow-event guards).  With the
    # default NullTracer each site costs one constant no-op; measure that
    # cost directly and bound the worst-case per-admission total against
    # the pipelined admit p95 — deterministic, unlike differencing two
    # noisy p95 runs.
    obs = _obs_disabled_overhead()
    obs_bound_ms = (
        obs["hooks_per_admit_bound"]
        * max(obs["span_ns"], obs["guard_ns"]) / 1e6
    )
    obs["overhead_ms_per_admit_bound"] = obs_bound_ms

    criterion = {
        # deeper windows mean staler optimistic solves; the gates assert
        # the overlap never costs tail latency or admitted work
        "pipeline_p95_depth4_le_1p1x_depth1":
            d_deep["admit_ms_p95"] <= 1.1 * d_sync["admit_ms_p95"],
        "pipeline_admission_within_2pts":
            abs(d_deep["steady_admission_rate"]
                - d_sync["steady_admission_rate"]) <= 0.02,
        # telemetry off == telemetry absent: the disabled hooks' bounded
        # per-admission cost stays within 3% of the pipelined admit p95
        "obs_disabled_overhead_within_3pct":
            obs_bound_ms <= 0.03 * d_deep["admit_ms_p95"],
    }
    record = {
        "obs_overhead": obs,
        "baseline": base,
        "sweep": sweep,
        "knee": {
            "rate": knee["rate"],
            "hold": knee["hold"],
            "steady_admission_rate": knee["steady_admission_rate"],
            "threshold": knee_threshold,
            # False = the sweep never crossed the threshold and the "knee"
            # is just its last point; downstream overload scenarios (and
            # their CI gates) are then meaningless — widen the sweep.
            "saturated": found is not None,
        },
        "pipeline": pipeline,
        "criterion": criterion,
    }
    if out_path is not None:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
    return record


def _superstep_stats(supersteps: dict) -> dict:
    """{mode: {rounds: count}} -> {mode: {solves, mean, max}} (tolerates
    the string keys a JSON round-trip introduces)."""
    out = {}
    for mode, buckets in supersteps.items():
        total = sum(buckets.values())
        out[mode] = {
            "solves": total,
            "mean": sum(int(r) * c for r, c in buckets.items())
            / max(total, 1),
            "max": max((int(r) for r in buckets), default=0),
        }
    return out


def run_cache_fastpath(*, n: int = 24, p: int = 5, rate: float = 16.0,
                       hold: float = 0.6, horizon: float = 12.0,
                       churn_hold: float = 2.0, churn_fail_every: float = 2.5,
                       warmup: float = 2.0, repeat_pool: int = 6,
                       reps: int = 2, seed: int = 11,
                       use_kernel: bool = True):
    """Repeat-heavy streaming point, incremental fast path on vs off.

    Two workload phases, matching the two tiers:

    - **steady** (the p95 gate): the arrival stream cycles
      ``repeat_pool`` request shapes below the knee with no churn and a
      short ``hold``, so repeats mostly find the residual their cached
      mapping was committed against — tier-1 hits replace the DP with an
      O(p) revalidation and the admit tail collapses.  min-of-reps on
      the p95 (the robust floor; everything above it is interference).
    - **churn** (the superstep gate): same pool under periodic node
      failure and a longer hold, so entries go stale and the tier-2
      warm-started bounded correction path runs; its superstep buckets
      must sit strictly below the cold fixpoint's worst case (the
      ``max_correction_supersteps`` fuse, vs the rounds a cold batch
      solve actually takes).

    Gates in ``criterion`` (merged into BENCH_streaming.json):
    cache-on admit p95 <= 0.5x cache-off; lookup hit rate >= 0.5;
    steady-state admission rate within 1 point of the cold path; warm
    solves report strictly fewer supersteps than cold.
    """
    def _best(cache, **kw):
        best = None
        for _ in range(max(1, reps)):
            rec = run_streaming(
                n=n, p=p, rate=rate, horizon=horizon, warmup=warmup,
                seed=seed, use_kernel=use_kernel, cache=cache,
                repeat_pool=repeat_pool, out_path=None, **kw)
            if (best is None
                    or rec["admit_ms_p95_steady"]
                    < best["admit_ms_p95_steady"]):
                best = rec
        return best

    quiet = dict(hold=hold, fail_every=4 * horizon)  # no churn in-horizon
    off = _best(False, **quiet)
    on = _best(True, **quiet)
    churn = _best(True, hold=churn_hold, fail_every=churn_fail_every)
    ss = _superstep_stats(churn["supersteps"])
    warm, cold = ss.get("warm"), ss.get("cold")
    keep = ("admit_ms_mean", "admit_ms_p95", "admit_ms_mean_steady",
            "admit_ms_p95_steady", "steady_admission_rate",
            "solves", "cache_hits", "cache_misses", "cache_stale",
            "cache_neg_hits", "hit_rate", "warm_solves", "warm_fallbacks",
            "supersteps", "stale_batches", "batch_conflicts")
    record = {
        "n": n, "p": p, "rate": rate, "hold": hold, "horizon": horizon,
        "churn_hold": churn_hold, "churn_fail_every": churn_fail_every,
        "repeat_pool": repeat_pool, "reps": reps,
        "off": {k: off[k] for k in keep},
        "on": {k: on[k] for k in keep},
        "churn": {k: churn[k] for k in keep},
        "p95_ratio": on["admit_ms_p95_steady"]
        / max(off["admit_ms_p95_steady"], 1e-9),
        "superstep_stats": ss,
        "criterion": {
            "cache_p95_le_0p5x_off":
                on["admit_ms_p95_steady"]
                <= 0.5 * off["admit_ms_p95_steady"],
            "cache_hit_rate_ge_0p5": on["hit_rate"] >= 0.5,
            "cache_admission_within_1pt":
                abs(on["steady_admission_rate"]
                    - off["steady_admission_rate"]) <= 0.01,
            "warm_supersteps_lt_cold": bool(
                warm and cold and warm["max"] < cold["max"]),
        },
    }
    return record


def merge_cache_fastpath(swrec: dict, crec: dict,
                         out_path: str | None = "BENCH_streaming.json"
                         ) -> dict:
    """Fold the cache on/off comparison into the streaming record (its
    gates join the record-level ``criterion`` the CI fast lane asserts)."""
    swrec["cache"] = crec
    swrec["criterion"].update(crec["criterion"])
    if out_path is not None:
        with open(out_path, "w") as f:
            json.dump(swrec, f, indent=2)
    return swrec


def _obs_disabled_overhead(iters: int = 50_000) -> dict:
    """Per-site cost of the telemetry plane when DISABLED (the default):
    one ``NULL.span(...)`` context entry/exit, and one ``tracer.enabled``
    guard check — the only work any hot path pays without a live tracer."""
    from repro.obs import NULL

    t0 = time.perf_counter()
    for _ in range(iters):
        with NULL.span("bench", track="t", cat="c", k=1):
            pass
    span_ns = (time.perf_counter() - t0) / iters * 1e9
    t0 = time.perf_counter()
    for _ in range(iters):
        if NULL.enabled:
            NULL.flow_point(1, "bench")
    guard_ns = (time.perf_counter() - t0) / iters * 1e9
    return {
        "span_ns": round(span_ns, 1),
        "guard_ns": round(guard_ns, 1),
        # generous upper bound on instrumentation sites one admission
        # crosses: pump round + dispatch + solve + validate/commit +
        # conflict re-solve spans, plus every flow-event guard
        "hooks_per_admit_bound": 16,
    }


def run_fairness(*, knee_rate: float, n: int = 24, p: int = 5,
                 overload_factor: float = 1.5, weights=(3.0, 1.0),
                 hold: float = 4.0, horizon: float = 8.0, tick: float = 0.25,
                 fail_every: float = 2.5, warmup: float = 2.0,
                 micro_batch: int = 32, seed: int = 11,
                 use_kernel: bool = True,
                 out_path: str | None = "BENCH_fairness.json"):
    """Two-tenant overload scenario past the admission knee, weights 3:1.

    Runs on the *same* network and request distribution as the overload
    sweep, at ``overload_factor`` x the knee rate.  One shared Poisson
    arrival process is split round-robin between the tenants, so both offer
    exactly the same load and arrival order carries no information about
    entitlement:

    - **weighted** — the control plane's weighted max-min scheduler; the
      steady-state standing committed capacity should split by weight
      (3:1 -> fractions 0.75/0.25) within ~10%.
    - **fcfs** — the bare ``OnlinePlacer`` admitting in arrival order; both
      tenants then hold ~equal capacity, >25% off their weighted shares.

    Ends with the background-defrag exercise on the churn-fragmented
    network: after the node fail/restore cycles, restore everything and run
    one ``defrag()`` pass — it must strictly improve the global objective
    (or no-op) and re-admit previously-rejected (queued) requests.
    """
    from repro.service import ControlPlane, FairSharePolicy

    rng = np.random.default_rng(seed)
    rg = waxman(n, seed=seed)
    rate_total = float(knee_rate) * overload_factor
    names = ("gold", "bronze")
    w = dict(zip(names, weights))
    frac = {t: w[t] / sum(w.values()) for t in names}
    times = _poisson_times(rng, rate_total, horizon)
    stream = _request_stream(rg, len(times), p, seed0=seed * 977)
    # round-robin split: identical offered load, interleaved arrival order
    arrivals = {t: [] for t in names}
    reqs = {t: [] for t in names}
    for k, (at, df) in enumerate(zip(times, stream)):
        t = names[k % 2]
        arrivals[t].append(at)
        reqs[t].append(df)

    def _churn_tick(placer, now, state):
        """Shared fail/restore cycle: restore the previous casualty and pick
        the busiest intermediate node for the caller to fail."""
        if now < state["next_fail"]:
            return
        state["next_fail"] += fail_every
        if state["failed"] is not None:
            placer.restore_node(state["failed"])
            state["failed"] = None
        load = np.zeros(n)
        for tk in placer.tickets.values():
            for v in tk.mapping.route:
                if v not in (tk.df.src, tk.df.dst):
                    load[v] += 1
        if load.max() > 0:
            state["failed"] = int(load.argmax())
            state["cycles"] += 1
            return state["failed"]
        return None

    # ---- weighted: the control plane ------------------------------------
    cp = ControlPlane(rg, policy=FairSharePolicy(slack=0.4),
                      micro_batch=micro_batch, max_attempts=10,
                      use_kernel=use_kernel)
    # one warmup covers both runs: the FCFS placer below hits the same
    # process-wide jit cache entries
    cp.warmup(max_batch=int(max(micro_batch, 4 * rate_total * tick)), p=p)
    for t in names:
        cp.register_tenant(t, weight=w[t])
    # departure entries carry (rid, tid): a request displaced to the queue
    # and later re-admitted gets a NEW ticket (new tid) and a new timer —
    # its stale entry must not release it early.  In-place re-mapping
    # preserves the tid, so those entries stay valid.
    dep: list[tuple[float, int, int]] = []
    scheduled: dict[int, int] = {}  # rid -> tid of the armed entry
    samples = {t: [] for t in names}
    backlogged_ticks = total_ticks = 0
    state = {"next_fail": fail_every, "failed": None, "cycles": 0}
    idx = {t: 0 for t in names}
    drng = np.random.default_rng(seed + 1)

    def _arm(tk, when):
        rid = cp.rid_of(tk)
        if rid is not None and scheduled.get(rid) != tk.tid:
            scheduled[rid] = tk.tid
            heapq.heappush(dep, (when, rid, tk.tid))

    now = 0.0
    while now < horizon:
        now = min(now + tick, horizon)
        while dep and dep[0][0] <= now:
            _, rid, tid = heapq.heappop(dep)
            entry = cp.active.get(rid)
            if entry is not None and entry[1].tid == tid:
                cp.release(rid)
                scheduled.pop(rid, None)
        victim = _churn_tick(cp.placer, now, state)
        if victim is not None:
            alive, _requeued = cp.fail_node(victim)
            for tk in alive:  # preemptive rescues carry a NEW tid: arm them
                _arm(tk, now + drng.exponential(hold))
        for t in names:
            while idx[t] < len(arrivals[t]) and arrivals[t][idx[t]] <= now:
                cp.submit(t, reqs[t][idx[t]])
                idx[t] += 1
        for tk in cp.pump():
            _arm(tk, now + drng.exponential(hold))
        if now >= warmup:
            held = cp.committed_capacity()
            for t in names:
                samples[t].append(held[t])
            total_ticks += 1
            backlogged_ticks += all(
                cp.tenants[t].queue for t in names
            )
    cp.check_invariants()

    def _shares(mean_held):
        total = sum(mean_held.values())
        actual = {t: mean_held[t] / max(total, 1e-12) for t in names}
        dev = {t: abs(actual[t] - frac[t]) / frac[t] for t in names}
        return actual, dev

    mean_w = {t: float(np.mean(samples[t])) for t in names}
    actual_w, dev_w = _shares(mean_w)
    weighted = {
        "mean_committed": mean_w,
        "actual_fractions": actual_w,
        "target_fractions": frac,
        "deviation": dev_w,
        "max_deviation": max(dev_w.values()),
        "backlogged_frac": backlogged_ticks / max(total_ticks, 1),
        "preempted": cp.placer.stats.preempted,
        "dropped": cp.conservation()["dropped"],
        "queued_end": cp.conservation()["queued"],
        "conservation_ok": cp.conservation()["ok"],
    }

    # ---- defrag on the churn-fragmented end state -----------------------
    if state["failed"] is not None:  # run against the fully-restored net
        cp.restore_node(state["failed"])
        state["failed"] = None
    queued_before = cp.conservation()["queued"]
    res = cp.defrag()
    cp.check_invariants()
    defrag_rec = {
        "churn_cycles": state["cycles"],
        "standing": res.standing,
        "queued_before": queued_before,
        "committed": res.committed,
        "repacked": res.repacked,
        "objective_before": list(res.objective_before),
        "objective_after": list(res.objective_after),
        "moved": res.moved,
        "readmitted": len(res.readmitted),
        "never_regresses": res.objective_after >= res.objective_before,
        "invariants_ok": True,
    }

    # ---- FCFS baseline: same traces through the bare placer -------------
    placer = OnlinePlacer(rg, use_kernel=use_kernel)
    merged = sorted(
        (at, t, i)
        for t in names for i, at in enumerate(arrivals[t])
    )
    dep2: list[tuple[float, int]] = []
    samples2 = {t: [] for t in names}
    state2 = {"next_fail": fail_every, "failed": None, "cycles": 0}
    drng2 = np.random.default_rng(seed + 1)
    j = 0
    now = 0.0
    while now < horizon:
        now = min(now + tick, horizon)
        while dep2 and dep2[0][0] <= now:
            _, tid = heapq.heappop(dep2)
            if tid in placer.tickets:
                placer.release(tid)
        victim = _churn_tick(placer, now, state2)
        if victim is not None:
            placer.fail_node(victim)
        batch, metas = [], []
        while j < len(merged) and merged[j][0] <= now:
            _, t, i = merged[j]
            batch.append(reqs[t][i])
            metas.append((t, 0))
            j += 1
        for tk in placer.admit_many(batch, metas=metas):
            if tk is not None:
                heapq.heappush(dep2, (now + drng2.exponential(hold), tk.tid))
        if now >= warmup:
            held = {t: 0.0 for t in names}
            for tk in placer.tickets.values():
                held[tk.tenant] += float(np.sum(tk.df.creq))
            for t in names:
                samples2[t].append(held[t])
    placer.check_invariants()
    mean_f = {t: float(np.mean(samples2[t])) for t in names}
    actual_f, dev_f = _shares(mean_f)
    fcfs = {
        "mean_committed": mean_f,
        "actual_fractions": actual_f,
        "target_fractions": frac,
        "deviation": dev_f,
        "max_deviation": max(dev_f.values()),
    }

    record = {
        "n": n, "p": p, "knee_rate": float(knee_rate),
        "overload_factor": overload_factor, "rate_total": rate_total,
        "weights": w, "hold": hold, "horizon": horizon, "tick": tick,
        "fail_every": fail_every, "warmup": warmup,
        "micro_batch": micro_batch, "use_kernel": use_kernel,
        "weighted": weighted,
        "fcfs": fcfs,
        "defrag": defrag_rec,
        "criterion": {
            "weighted_within_10pct": weighted["max_deviation"] <= 0.10,
            "fcfs_deviation_gt_25pct": fcfs["max_deviation"] > 0.25,
            "defrag_never_regresses": defrag_rec["never_regresses"],
            "defrag_readmitted_any": defrag_rec["readmitted"] >= 1,
        },
    }
    if out_path is not None:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
    return record


def run():
    rows = run_archs()
    rec = run_online()
    rows.append({
        "name": "placement_online_service",
        "us_per_call": 1e6 * rec["batched_s"] / rec["n_requests"],
        "derived": (
            f"speedup_batched={rec['speedup']:.1f}x;"
            f"speedup_kernel={rec['speedup_kernel']:.1f}x;"
            f"admitted={rec['admitted']}/{rec['n_requests']};"
            f"agreement={rec['agreement']:.2f};"
            f"churn_remapped={rec['churn']['remapped']}/"
            f"{rec['churn']['displaced']}"
        ),
    })
    swrec = run_overload_sweep()
    swrec = merge_cache_fastpath(swrec, run_cache_fastpath())
    srec = swrec["baseline"]
    rows.append({
        "name": "placement_streaming_poisson",
        "us_per_call": 1e3 * srec["admit_ms_mean"],
        "derived": (
            f"admission_rate={srec['admission_rate']:.2f};"
            f"occupancy={srec['steady_state_occupancy']:.1f};"
            f"remap_ms_p95={srec['remap_ms_p95']:.1f};"
            f"dropped={srec['dropped']};"
            f"knee_rate={swrec['knee']['rate']:.0f}"
        ),
    })
    crec = swrec["cache"]
    rows.append({
        "name": "placement_cache_fastpath",
        "us_per_call": 1e3 * crec["on"]["admit_ms_mean"],
        "derived": (
            f"p95_ratio={crec['p95_ratio']:.2f};"
            f"hit_rate={crec['on']['hit_rate']:.2f};"
            f"warm_solves={crec['on']['warm_solves']};"
            f"solves_on={crec['on']['solves']};"
            f"solves_off={crec['off']['solves']}"
        ),
    })
    frec = run_fairness(knee_rate=swrec["knee"]["rate"])
    rows.append({
        "name": "placement_fairness_overload",
        "us_per_call": 0.0,
        "derived": (
            f"weighted_dev={frec['weighted']['max_deviation']:.3f};"
            f"fcfs_dev={frec['fcfs']['max_deviation']:.3f};"
            f"defrag_readmitted={frec['defrag']['readmitted']};"
            f"preempted={frec['weighted']['preempted']}"
        ),
    })
    return rows


if __name__ == "__main__":
    import argparse

    from repro.core.device import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="online + streaming + fairness only, small sizes "
                         "(CI artifact)")
    args = ap.parse_args()
    if args.smoke:
        rec = run_online(
            n=24, n_requests=64, micro_batch=64,
            curve_kwargs=dict(n_list=(16, 24), batch_list=(1, 8, 32),
                              reps=20),
        )
        swrec = run_overload_sweep(
            n=20, rates=(24.0, 48.0, 96.0, 192.0), horizon=5.0,
            baseline_rate=16.0,
        )
        swrec = merge_cache_fastpath(swrec, run_cache_fastpath(n=20))
        frec = run_fairness(knee_rate=swrec["knee"]["rate"], n=20,
                            horizon=6.0, warmup=2.0)
    else:
        rec = run_online()
        swrec = run_overload_sweep()
        swrec = merge_cache_fastpath(swrec, run_cache_fastpath())
        frec = run_fairness(knee_rate=swrec["knee"]["rate"])
    print(json.dumps(
        {"online": rec, "streaming": swrec, "fairness": frec}, indent=2))
