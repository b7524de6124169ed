"""Algorithms + local kernels: stored entries of the result a second of a job's wall
(counter ``spgemm.job.nnz_out`` over ``spgemm.job.jobs``, the program's own counts,
over the median job's wall; Mnnz/s): upstream's ``MultTime`` rate, ``BASELINE.json``'s
"SpGEMM nnz-out/sec".  ``mteps`` times a constant of the configuration."""

from chipbench.parts import counter
from chipbench.reading import median_ms


def read(ctx):
    out, jobs = counter("spgemm.job.nnz_out"), counter("spgemm.job.jobs")
    wall = median_ms(ctx.get("job_walls") or [])
    if not out or not jobs or not wall:
        return None
    return out / jobs / (wall * 1e-3) / 1e6
