"""Service-plane host ms per decision: harness wall time in plane calls less the placer's solve, overhead and conflict ms."""
from bench.harness import readers


def read(ctx):
    return readers.plane_self_ms(ctx)
