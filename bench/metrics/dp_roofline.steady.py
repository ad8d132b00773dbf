"""Share of the DP's HBM roofline: least time for its lat/bw traffic over its device time."""
from bench.harness import readers


def read(ctx):
    return readers.dp_roofline(ctx)
