"""Host busy time (ms per slab) of the replay-core thread: the window
minus its ``replay_wait`` host ranges (gets on the actor and feedback
queue, empty polls included), over the window's slabs.  It holds the
launches of the priority write-back and the ring insert."""
from bench.metrics._host_spans import busy_per_slab


def read(ctx):
    return busy_per_slab(ctx, "replay_wait")
