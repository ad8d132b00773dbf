"""Admit and drop decisions stamped in the window over its seconds."""
from bench.harness import stats


def read(ctx):
    return stats.rate(ctx["decisions"], ctx["window_s"])
