"""Algorithms + local kernels: a job's wall on the host's clock (the median over the
window's whole jobs, what ``mteps`` divides by) minus ``mcl_device_ms``: every iteration's
read of its chaos and counts, the symbolic round trips of the sparse iterations, the
launches and the read of the digest, in which the device waits (ms)."""

from chipbench.mclscopes import device_ms
from chipbench.reading import median_ms


def read(ctx):
    wall, busy = median_ms(ctx.get("job_walls") or []), device_ms(ctx)
    return wall - busy if wall is not None and busy is not None else None
