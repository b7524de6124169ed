"""Engine: stage ``execute`` in the open-loop cells, median over requests
(ms): the service part of a request's latency, beside ``queue_wait_ms``."""

from chipbench.reading import stage_ms


def read(ctx):
    return stage_ms(ctx, "execute")
