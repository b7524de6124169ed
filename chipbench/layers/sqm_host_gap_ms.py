"""Algorithms + local kernels: a mesh job's wall on the host's clock (the median over
the window's whole jobs, what ``mteps`` divides by) minus ``sqm_device_ms``: the
symbolic pass's round trips to the host, the launches of the job's programs, the read
of the tiles' counts before the pack and of the digest, in which even the busiest chip
waits (ms)."""

from chipbench.reading import median_ms
from chipbench.sqmscopes import device_ms


def read(ctx):
    wall, busy = median_ms(ctx.get("job_walls") or []), device_ms(ctx)
    return wall - busy if wall is not None and busy is not None else None
