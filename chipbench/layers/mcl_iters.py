"""Algorithms + local kernels: iterations a job runs until chaos is under ``eps``
(counter ``mcl.job.iters``, summed over its tiers, over ``mcl.job.jobs``: the program's
own counts)."""

from chipbench.parts import counter


def read(ctx):
    iters, jobs = counter("mcl.job.iters"), counter("mcl.job.jobs")
    return iters / jobs if iters and jobs else None
