"""Device time (ms) of the learner's fused slab of updates: the mean duration of the
``learn_slab`` program's executions in the window, from the device trace."""
from bench.metrics._program_ms import mean_ms


def read(ctx):
    return mean_ms(ctx, "learn_slab")
