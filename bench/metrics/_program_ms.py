"""Mean device time of one execution of a named XLA program, averaged
over the devices that ran it (shared by the per-program readers)."""
from bench import trace


def mean_ms(ctx, program: str):
    per_dev = []
    for dev in ctx.trace.devices.values():
        t = trace.program_times(dev, program, ctx.window)
        if t:
            per_dev.append(sum(t) / len(t))
    if not per_dev:
        return None
    return sum(per_dev) / len(per_dev) * 1e-6
