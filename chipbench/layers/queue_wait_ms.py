"""Scheduler / batcher: stage ``queue_wait``, median over requests (ms)."""

from chipbench.reading import stage_ms


def read(ctx):
    return stage_ms(ctx, "queue_wait")
