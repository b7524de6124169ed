"""Algorithms + local kernels: device time of one whole execution of the matching
job's program (``jit__mcm_job_ell``, the program that took most device time: Karp-Sipser
rounds and augmenting phases, both loops on the device) in the profiled slice, mean over
its whole executions (ms)."""

from chipbench.reading import device_ms as read  # noqa: F401
