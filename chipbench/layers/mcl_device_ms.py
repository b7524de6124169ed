"""Algorithms + local kernels: the device's busy time inside one whole clustering job
(every iteration's programs, the walk between the dense state and tuples, the
interpretation: the union of their operations' intervals between the start and the end of
the program's own ``mcl.job`` annotation), mean over the jobs the profiled slice holds
whole (ms)."""

from chipbench.mclscopes import device_ms as read  # noqa: F401
