"""Algorithms + local kernels: device time of one whole execution of the
triangle-count job's program (``jit_tc_edgeharvest_bits``, the program that took most
device time) in the profiled slice, mean over its whole executions as ``k2_device_ms``
is (ms)."""

from chipbench.reading import device_ms as read  # noqa: F401
