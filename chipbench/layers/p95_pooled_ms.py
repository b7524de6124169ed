"""Scheduler / batcher: the 95th percentile of latency from the scheduled
send over ALL requests of the window at once (ms).  ``p95_ms`` is the median
of the same taken block by block; where the two part, one stall or one
queueing episode filled this tail (``gen_late_max_ms`` says which)."""


def read(ctx):
    return ctx.get("p95_pooled_ms")
