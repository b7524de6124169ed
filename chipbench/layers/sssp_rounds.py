"""Algorithms + local kernels: Bellman-Ford rounds of a served kernel-3 batch, the
round that changed nothing included (counter ``serve.sssp.rounds`` over
``serve.sssp.batches``, mean over batches).  A batch runs the largest of its lanes."""

from chipbench.k3scopes import rounds_per_batch as read  # noqa: F401
