"""Algorithms + local kernels: the device's busy time inside one whole sparse-product
job (the programs of its symbolic pass, its numeric phase and its digest together: the
union of their operations' intervals between the start and the end of the program's own
``spgemm.job`` annotation), mean over the jobs the profiled slice holds whole (ms)."""

from chipbench.sqscopes import device_ms as read  # noqa: F401
