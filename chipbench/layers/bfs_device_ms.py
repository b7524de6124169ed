"""Algorithms + local kernels: device time of one served BFS batch program
in the profiled slice (ms)."""

from chipbench.reading import device_ms as read  # noqa: F401
