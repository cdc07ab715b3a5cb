"""Host busy time (ms per slab) of the prefetch thread: the window
minus its ``prefetch_wait`` host ranges (puts on the slab queue, the
warm-up sleep, the gate), over the window's slabs.  It holds the slab
draw's launch, the host syncs of that thread and the GIL waits."""
from bench.metrics._host_spans import busy_per_slab


def read(ctx):
    return busy_per_slab(ctx, "prefetch_wait")
