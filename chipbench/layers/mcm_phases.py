"""Algorithms + local kernels: augmenting phases of a job, the one that augments
nothing included (counter ``models.mcm.phases`` over ``models.mcm.jobs``).  The graph
fixes it: every job starts from the empty matching."""

from chipbench.mcmwork import phases_per_job as read  # noqa: F401
