"""Host ms per DP solve blocked on the batched DP's answer (timing.dp_wait_ms / placer.solves)."""
from bench.harness import program_counters


def read(ctx):
    return program_counters.dp_wait_ms(ctx)
