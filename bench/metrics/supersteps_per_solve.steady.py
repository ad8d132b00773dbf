"""DP supersteps per solve, from the program's superstep histograms."""
from bench.harness import readers


def read(ctx):
    return readers.supersteps_per_solve(ctx)
