"""Algorithms + local kernels: levels a wave of the deep-graph cell ran (the
batch's ``niter``, from the program's own count on its stage records), mean over
the widest lane's batches."""

from chipbench.deepwork import levels as read  # noqa: F401
