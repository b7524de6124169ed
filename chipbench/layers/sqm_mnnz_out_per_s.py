"""Algorithms + local kernels: stored entries of the result, all tiles together, a
second of a mesh job's wall (counter ``spgemm.job.nnz_out`` over ``spgemm.job.jobs``,
the program's own counts, over the median job's wall; Mnnz/s): upstream's ``MultTime``
rate on a grid, ``BASELINE.json``'s "SpGEMM nnz-out/sec".  ``mteps`` times a constant
of the configuration; over the chips, the per-chip rate the one-chip cell's
``sq_mnnz_out_per_s`` is compared with."""

from chipbench.parts import counter
from chipbench.reading import median_ms


def read(ctx):
    out, jobs = counter("spgemm.job.nnz_out"), counter("spgemm.job.jobs")
    wall = median_ms(ctx.get("job_walls") or [])
    if not out or not jobs or not wall:
        return None
    return out / jobs / (wall * 1e-3) / 1e6
