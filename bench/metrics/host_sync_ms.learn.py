"""Time (ms per slab) the service's threads spend blocked in host reads
of device values: the ``host_sync`` host ranges of every thread inside
the window, summed, over the window's slabs."""
from bench.metrics._host_spans import span_per_slab


def read(ctx):
    return span_per_slab(ctx, "host_sync")
