"""Profiler trace of the window, and its reduction to device numbers.

``capture`` records the window with ``jax.profiler``; ``read`` keeps from
the ``.xplane.pb`` it writes the chips' module executions and the
harness's host spans, as plain ``{plane: {line: [Event]}}``; ``reduce``
works on that form alone, so a small recorded trace can check it.

Device time is the union of the program executions on each chip's "XLA
Modules" line, inside the window the harness marks with its
``bench.window`` annotation, averaged over chips.  A module's name is the
jitted function's (``jit__lambda(<fingerprint>)`` counts as
``jit__lambda``).  Each idle gap of the first chip is named by the harness
span (``bench.pump``, ``bench.submit``, ``bench.release``, ``bench.churn``,
``bench.sleep``) that covers most of it.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import os
import shutil

Event = collections.namedtuple("Event", "name start dur stats")  # ns

DEVICE_PREFIX = "/device:"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@contextlib.contextmanager
def capture(trace_dir: str):
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def read(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}: {paths}")
    planes: dict = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            if device and line.name != MODULES_LINE:
                continue
            keep = [Event(e.name, int(e.start_ns), int(e.duration_ns), {})
                    for e in line.events
                    if device or e.name.startswith(SPAN_PREFIX)]
            if keep:
                lines.setdefault(line.name, []).extend(keep)
    return planes


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def window_of(planes: dict) -> tuple:
    for lines in planes.values():
        for events in lines.values():
            for ev in events:
                if ev.name == WINDOW_SPAN:
                    return ev.start, ev.start + ev.dur
    raise RuntimeError(f"no {WINDOW_SPAN} span in the trace")


def device_modules(planes: dict) -> dict:
    """Chip plane name -> its "XLA Modules" events."""
    return {name: lines[MODULES_LINE] for name, lines in planes.items()
            if name.startswith(DEVICE_PREFIX) and MODULES_LINE in lines}


def module_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def reduce(planes: dict, *, top: int = 10) -> dict:
    """``busy_s`` (mean over chips), ``window_s``, and the ``top`` modules
    by device time (summed over chips) and idle gaps."""
    lo, hi = window_of(planes)
    chips = device_modules(planes)
    busy, per_module = [], collections.Counter()
    first = None
    for name in sorted(chips):
        mods = [ev for ev in chips[name] if ev.start + ev.dur > lo
                and ev.start < hi]
        u = _clip(_union((ev.start, ev.start + ev.dur) for ev in mods), lo, hi)
        busy.append(sum(e - s for s, e in u))
        if first is None:
            first = u
        for ev in mods:
            d = min(ev.start + ev.dur, hi) - max(ev.start, lo)
            per_module[module_name(ev.name)] += d
    spans = [ev for lines in planes.values() for evs in lines.values()
             for ev in evs
             if ev.name.startswith(SPAN_PREFIX) and ev.name != WINDOW_SPAN]
    gaps = []
    edges = [lo] + [x for s, e in (first or []) for x in (s, e)] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        cover = collections.Counter()
        for sp in spans:
            o = min(e, sp.start + sp.dur) - max(s, sp.start)
            if o > 0:
                cover[sp.name] += o
        label = cover.most_common(1)[0][0] if cover else "outside.bench"
        gaps.append([label, (e - s) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    return {
        "chips": len(busy),
        "busy_s": (sum(busy) / len(busy)) * 1e-9 if busy else 0.0,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[k, v * 1e-9] for k, v in per_module.most_common(top)],
        "idle_gaps": gaps[:top],
    }
