"""Device time (ms) of one closed-loop draw (indices, weights and stacked batch): the mean duration of the
``replay_draw`` program's executions in the window, from the device trace."""
from bench.metrics._program_ms import mean_ms


def read(ctx):
    return mean_ms(ctx, "replay_draw")
