"""Host ms per DP solve backtracking the batched DP's answer into mappings (timing.reconstruct_ms / placer.solves)."""
from bench.harness import program_counters


def read(ctx):
    return program_counters.reconstruct_ms(ctx)
