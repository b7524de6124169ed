"""Algorithms + local kernels: self time of the served BC program under the scopes
``ell.bucket<i>/gather`` and ``ell.bucket<i>/fold`` (forward and backward sweeps
alike) over its device time, whole executions of the profiled slice (%)."""

from chipbench.bcscopes import share as read  # noqa: F401
