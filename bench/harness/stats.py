"""Percentile and rate arithmetic of the benchmark."""
from __future__ import annotations

import numpy as np


def latencies_ms(due: list, decided: list, end_s: float) -> np.ndarray:
    """Due time -> decision, in ms, for every request due in the window,
    whether it was decided in the window or in the drain after it: a late
    answer's latency counts the wait.  A request never decided
    (``decided`` is None) enters at its age at ``end_s``, when the drain
    stopped."""
    return np.asarray([1e3 * ((end_s if t is None else t) - d)
                       for d, t in zip(due, decided)], np.float64)


def percentile(values: np.ndarray, q: float) -> float:
    """Linearly interpolated percentile (``numpy.percentile``'s default),
    as ``benchmarks/bench_trace`` reports its admit latencies."""
    return float(np.percentile(values, q))


def rate(count: int, seconds: float) -> float:
    return count / seconds

