"""Mean ms from a request's (re)queue to its dispatch into a pump (plane.queue_wait_ms)."""
from bench.harness import program_counters


def read(ctx):
    return program_counters.queue_wait_ms(ctx)
