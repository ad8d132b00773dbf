"""Arithmetic of the metric readers that read the program's own counters
at the placer's inner boundaries (``ctx`` as in ``readers``).

A counter is found under its name alone or with labels
(``residual.rebuild_ms{site=commit}``); labeled series are summed.  A
program that does not keep a counter has no key for it, and its reader
returns None.
"""
from __future__ import annotations


def series(ctx, name: str) -> list:
    """The window's values of every labeled series of ``name``."""
    return [v for k, v in ctx["counters"].items()
            if k == name or k.startswith(name + "{")]


def total(ctx, name: str):
    """Sum of the counter over its labels, or None where it is missing."""
    vals = series(ctx, name)
    return float(sum(vals)) if vals else None


def per(ctx, name: str, *over: str):
    """``name`` over the summed counters ``over``: None when ``name`` is
    missing or the denominator is 0."""
    num = total(ctx, name)
    den = sum(total(ctx, o) or 0.0 for o in over)
    return num / den if num is not None and den else None


def residual_rebuild_ms(ctx):
    """Host ms rebuilding the dense residual network, per decision."""
    ms, n = total(ctx, "residual.rebuild_ms"), ctx["decisions"]
    return ms / n if ms is not None and n else None


def dp_wait_ms(ctx):
    """Host ms blocked on the batched DP's answer, per solve."""
    return per(ctx, "timing.dp_wait_ms", "placer.solves")


def reconstruct_ms(ctx):
    """Host ms backtracking the batched DP's answer into mappings, per
    solve."""
    return per(ctx, "timing.reconstruct_ms", "placer.solves")


def queue_wait_ms(ctx):
    """Mean ms from a request's (re)queue to its dispatch, over the
    window's dispatches (the ``plane.queue_wait_ms`` histogram)."""
    hists = [v for v in series(ctx, "plane.queue_wait_ms")
             if isinstance(v, tuple)]
    count = sum(h[0] for h in hists)
    return sum(h[1] for h in hists) / count if count else None


def remap_ms(ctx):
    """Placer ms re-admitting tickets a failure displaced, per displaced
    ticket (remapped or dropped)."""
    return per(ctx, "placer.remap_ms", "placer.remapped", "placer.dropped")
