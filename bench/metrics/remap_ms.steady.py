"""Placer ms per ticket displaced by the failure, re-admission DP included (placer.remap_ms / (remapped + dropped))."""
from bench.harness import program_counters


def read(ctx):
    return program_counters.remap_ms(ctx)
