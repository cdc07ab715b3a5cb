"""Time (ms per slab) the learner thread waits for a slab on the
prefetch queue: the ``learner_wait`` host ranges (all of
``Learner._get_slab``) inside the window, over the window's slabs.
Near the slab period, the learner is starved by the draw; near 0, the
learner's own launches set the pace."""
from bench.metrics._host_spans import span_per_slab


def read(ctx):
    return span_per_slab(ctx, "learner_wait")
