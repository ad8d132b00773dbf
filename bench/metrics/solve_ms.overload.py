"""ms per DP solve: dispatch, device wait and reconstruction (solve_ms / solves)."""
from bench.harness import readers


def read(ctx):
    return readers.solve_ms(ctx)
