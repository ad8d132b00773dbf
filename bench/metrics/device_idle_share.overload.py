"""Share of the traced window in which no op ran on the chip."""
from bench.harness import readers


def read(ctx):
    return readers.device_idle_share(ctx)
