"""Algorithms + local kernels: a job's wall on the host's clock (the median over the
window's whole jobs, what ``mteps`` divides by) minus ``sq_device_ms``: the symbolic
pass's round trips to the host, the launches of the numeric phase's programs and the
read of the digest, in which the device waits (ms)."""

from chipbench.reading import median_ms
from chipbench.sqscopes import device_ms


def read(ctx):
    wall, busy = median_ms(ctx.get("job_walls") or []), device_ms(ctx)
    return wall - busy if wall is not None and busy is not None else None
