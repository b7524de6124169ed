"""Algorithms + local kernels: self time of the served kernel-3 program under the
scopes ``ell.bucket<i>/gather`` and ``ell.bucket<i>/fold`` (rounds and parents pass
alike) over its device time, whole executions of the profiled slice (%)."""

from chipbench.k3scopes import share as read  # noqa: F401
