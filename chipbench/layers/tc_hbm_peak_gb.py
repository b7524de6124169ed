"""Algorithms + local kernels: the chip's peak bytes over the run (GB): the peak of live
arrays, ``memory_stats()["peak_bytes_in_use"]`` as ``hbm_peak_gb`` reads it for the
served cells (that entry moves ``qps`` and cannot list a cell that reports ``mteps``),
PLUS ``peak_bytes_reserved``, where the v5e's allocator keeps a running program's
temporaries: the ``n * n / 8``-byte table and a step's two gathered chunks live only
inside the job's program, and the first reading alone does not see them (0.18 GB of
9.4: PERF.md section 7).  The two peaks need not fall together, so the sum is an upper
bound; here the live arrays (the ELL matrix, the ``SpParMat``) are there all run."""


def read(ctx):
    import jax

    in_use = ctx["device"].get("memory_peak_bytes") or 0
    reserved = max(
        (int((d.memory_stats() or {}).get("peak_bytes_reserved", 0))
         for d in jax.devices()), default=0)
    return (in_use + reserved) / 1e9 if in_use + reserved else None
