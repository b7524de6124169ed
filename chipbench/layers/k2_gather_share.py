"""Algorithms + local kernels: self time of the ``bfs_batch_compact`` program
under the scopes ``ell.bucket<i>/gather`` and ``ell.bucket<i>/fold`` (the level
loop and the parents pass) over its device time (%)."""

from chipbench.scopes import share as read  # noqa: F401
