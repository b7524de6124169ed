"""Algorithms + local kernels: slots the window's batches gathered (label ``slots``
of their stage records) over the requests they answered, in millions: what an answer
costs in swept indices when lanes run partly empty.  The traced run logs the family
class by class."""

from chipbench import ellwork


def read(ctx):
    ellwork.log_by_class(ctx)
    return ellwork.mslots_per_query(ctx)
