"""Algorithms + local kernels: Graph500 kernel-2 aggregate of the window,
all traversed edges of whole batches over first launch to last readback
(Medges/s).  ``mteps`` is the median over batches; the aggregate also moves
by 1 % with every batch that runs one BFS level more or fewer."""


def read(ctx):
    return ctx.get("mteps_aggregate")
