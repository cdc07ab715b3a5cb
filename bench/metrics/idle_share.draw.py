"""Idle share of the device over the draw loop's window, from the device
trace: 100 * (1 - busy / window)."""
from bench import trace


def read(ctx):
    return 100.0 * trace.idle_share(ctx.trace.devices[0], ctx.window)
