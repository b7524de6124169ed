"""Algorithms + local kernels: a job's wall on the host's clock (the median over the
window's whole jobs, what ``mteps`` divides by) minus ``mcm_device_ms``: the launch, the
read of both mate vectors and of the counts, in which the device waits (ms)."""

from chipbench.reading import device_ms, median_ms


def read(ctx):
    wall, busy = median_ms(ctx.get("job_walls") or []), device_ms(ctx)
    return wall - busy if wall is not None and busy is not None else None
