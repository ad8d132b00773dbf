"""Median, over the requests due in the window, of due time -> decision,
in the window or in the drain after it."""
from bench.harness import stats


def read(ctx):
    lat = ctx["latencies_ms"]
    return stats.percentile(lat, 50) if len(lat) else None
