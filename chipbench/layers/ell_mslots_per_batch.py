"""Algorithms + local kernels: slots the ELL class loop GATHERED in a served batch,
the busiest tile's, in millions (label ``slots`` of the stage records, mean over the
window's batches of the width that did most of its work).  A change that sweeps fewer
indices lowers this one; one that makes an index cheaper lowers ``ell_ns_per_index``.
The traced run logs the family's counters class by class."""

from chipbench import ellwork


def read(ctx):
    ellwork.log_by_class(ctx)
    return ellwork.mslots_per_batch(ctx)
