"""Host ms per decision rebuilding the dense residual network (residual.rebuild_ms over all call sites)."""
from bench.harness import program_counters


def read(ctx):
    return program_counters.residual_rebuild_ms(ctx)
