"""Device time (ms) of one slab's priority write-back: the mean duration of the
``apply_feedback`` program's executions in the window, from the device trace."""
from bench.metrics._program_ms import mean_ms


def read(ctx):
    return mean_ms(ctx, "apply_feedback")
