"""Placer host ms per decision: overhead_ms + conflict_resolve_ms."""
from bench.harness import readers


def read(ctx):
    return readers.placer_host_ms(ctx)
