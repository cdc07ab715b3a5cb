"""Idle share of the device that runs the learner step (device 0), over
the window, from the device trace: 100 * (1 - busy / window)."""
from bench import trace


def read(ctx):
    return 100.0 * trace.idle_share(ctx.trace.devices[0], ctx.window)
