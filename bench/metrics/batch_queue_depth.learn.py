"""Mean depth of the prefetch -> learner slab queue, from the service's
registry histogram ``batch_queue_depth`` (one sample per item the replay
thread drains): near 0 means the learner waits on draws, near the
prefetch depth means draws wait on the learner."""


def read(ctx):
    return float(ctx.result["service_metrics"]["queue_depth"]["batch_mean"])
