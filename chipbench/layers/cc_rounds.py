"""Algorithms + local kernels: FastSV rounds of a job, the round that changed nothing
included (counter ``models.cc.rounds`` over ``models.cc.jobs``, mean over jobs).  The
graph fixes it: every job starts from ``f = iota``."""

from chipbench.ccscopes import rounds_per_job as read  # noqa: F401
