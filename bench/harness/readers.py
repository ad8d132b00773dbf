"""Arithmetic shared by the metric readers in ``bench/metrics``.

Each reader gets the run's context: ``window_s``; ``latencies_ms`` of the
requests due in the window, each to its decision, the drain's included;
``decisions`` (first decisions stamped in the
window); ``counters``, the program's metrics-registry counters over the
window (superstep histograms as ``(solves, supersteps)``); ``spans_s``,
the harness's host seconds per span over the window; ``n``; and, in a
traced run, ``trace`` (``devtrace.reduce``, clipped to the same window)
and ``peak`` (``peaks.peak``).  Counters, spans and trace all stop where
the window closes, before the drain.  A reader with nothing to read
returns None.
"""
from __future__ import annotations

PLANE_CALLS = ("bench.pump", "bench.release", "bench.churn")
PLACER_MS = ("timing.solve_ms", "timing.overhead_ms",
             "timing.conflict_resolve_ms")


def counter(ctx, name: str) -> float:
    return float(ctx["counters"].get(name, 0.0))


def per_decision_ms(ctx, ms: float):
    n = ctx["decisions"]
    return ms / n if n else None


def plane_self_ms(ctx):
    wall_ms = 1e3 * sum(ctx["spans_s"].get(s, 0.0) for s in PLANE_CALLS)
    return per_decision_ms(ctx, wall_ms - sum(counter(ctx, k)
                                              for k in PLACER_MS))


def placer_host_ms(ctx):
    return per_decision_ms(ctx, counter(ctx, "timing.overhead_ms")
                           + counter(ctx, "timing.conflict_resolve_ms"))


def solve_ms(ctx):
    solves = counter(ctx, "placer.solves")
    return counter(ctx, "timing.solve_ms") / solves if solves else None


def supersteps(ctx) -> tuple:
    """(solves, supersteps) summed over the solve modes."""
    solves = steps = 0.0
    for key, v in ctx["counters"].items():
        if key.startswith("engine.supersteps") and isinstance(v, tuple):
            solves += v[0]
            steps += v[1]
    return solves, steps


def supersteps_per_solve(ctx):
    solves, steps = supersteps(ctx)
    return steps / solves if solves else None


def dp_roofline(ctx):
    """Least time the DP's HBM traffic needs over the chip's busy time in
    the window, in %.  The traffic counted is the float32 ``lat`` and
    ``bw`` matrices (n x n each) read once per superstep of every solve;
    the per-request state is left out.  The time is all device time, the
    DP's and whatever else ran: no device module is picked out by name.
    Both leave the share a lower bound on the DP's own."""
    tr, pk = ctx.get("trace"), ctx.get("peak")
    if not tr or not pk or not tr["chips"]:
        return None
    dev_s = tr["busy_s"]
    _, steps = supersteps(ctx)
    if dev_s <= 0 or steps <= 0:
        return None
    least_s = steps * 8.0 * ctx["n"] ** 2 / pk["hbm_bytes_per_s"]
    return 100.0 * least_s / dev_s


def device_idle_share(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["chips"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
