"""Algorithms + local kernels: whole sweeps of the matrix a served BC batch runs,
forward and backward together (counter ``serve.bc.sweeps`` over
``serve.bc.batches``, mean over batches).  A batch runs the depth of its deepest
lane: one forward sweep a BFS level, one backward sweep a level but the roots'."""

from chipbench.bcscopes import sweeps_per_batch as read  # noqa: F401
