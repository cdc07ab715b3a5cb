"""Device time (ms) of the slab draw (sampler draw and FrameStore stacks of slab x batch rows; the sharded draw's collectives included): the mean duration of the
``sample_slab`` program's executions in the window, from the device trace."""
from bench.metrics._program_ms import mean_ms


def read(ctx):
    return mean_ms(ctx, "sample_slab")
