"""Algorithms + local kernels: device time of one whole execution of the served BC
program (``jit_serve_bc_w16``, the program that took most device time) in the
profiled slice, mean over its whole executions as ``bfs_device_ms`` is (ms)."""

from chipbench.reading import device_ms as read  # noqa: F401
