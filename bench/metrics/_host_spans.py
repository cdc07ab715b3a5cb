"""Host time of the service's threads per slab, from the host ranges of
the traced run (the program's ``span`` annotations), clipped to the
window (shared by the host-span readers).

Each thread of the async service covers every point where it blocks on
a queue, a sleep or a gate with its own wait span (``learner_wait``,
``prefetch_wait``, ``replay_wait``), so a thread's time outside them is
its host busy time: launches, Python work and time lost to the GIL.
``host_sync`` covers each host read of a device value, on any thread.
A program without these spans gives no reading (None)."""
from bench import trace


def span_ms(ctx, name: str):
    """Summed ms of the ``name`` ranges inside the window, or None when
    the window holds none."""
    iv = trace.clip([(s, e) for n, s, e in ctx.trace.spans if n == name],
                    ctx.window)
    if not iv:
        return None
    return trace.length(iv) * 1e-6


def span_per_slab(ctx, name: str):
    """The ``name`` ranges inside the window, per slab (ms)."""
    ms = span_ms(ctx, name)
    return None if ms is None else ms / ctx.result["slabs"]


def busy_per_slab(ctx, wait: str):
    """The window outside the thread's wait span ``wait``, per slab."""
    ms = span_ms(ctx, wait)
    if ms is None:
        return None
    window_ms = (ctx.window[1] - ctx.window[0]) * 1e-6
    return (window_ms - ms) / ctx.result["slabs"]
