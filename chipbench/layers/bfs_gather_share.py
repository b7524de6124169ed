"""Algorithms + local kernels: self time of the served BFS program under the
scopes ``ell.bucket<i>/gather`` and ``ell.bucket<i>/fold`` over its device time,
whole executions of the profiled slice (%)."""

from chipbench.scopes import share as read  # noqa: F401
