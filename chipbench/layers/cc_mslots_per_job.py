"""Algorithms + local kernels: slots a FastSV job's sweeps gathered, in millions
(counter ``ell.slots{kind=cc, mode=dense}`` over ``ell.batches{kind=cc}``): the rounds
that swept times the matrix's class slots, every job alike on one graph."""

from chipbench import ellwork


def read(ctx):
    ellwork.log_by_class(ctx, "cc")
    return ellwork.mslots_per_batch(ctx, "cc")
