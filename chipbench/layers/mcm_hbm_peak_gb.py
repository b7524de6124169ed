"""Algorithms + local kernels: the chip's peak bytes over the run (GB), as
``mcl_hbm_peak_gb`` reads it: the peak of live arrays (``peak_bytes_in_use``: the pattern
both ways as ELL buckets and column lists) PLUS ``peak_bytes_reserved``, where the v5e's
allocator keeps a running program's temporaries.  The two peaks need not fall together,
so the sum is an upper bound."""


def read(ctx):
    import jax

    in_use = ctx["device"].get("memory_peak_bytes") or 0
    reserved = max(
        (int((d.memory_stats() or {}).get("peak_bytes_reserved", 0))
         for d in jax.devices()), default=0)
    return (in_use + reserved) / 1e9 if in_use + reserved else None
