"""Algorithms + local kernels: device time of one ``bfs_batch_compact``
program in the profiled slice (ms)."""

from chipbench.reading import device_ms as read  # noqa: F401
