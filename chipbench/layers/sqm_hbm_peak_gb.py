"""Algorithms + local kernels: the FULLEST chip's peak bytes over the run (GB), as
``sq_hbm_peak_gb`` reads it but a device at a time: the peak of its live arrays
(``peak_bytes_in_use``: its tiles of the operand, the windows' slots before the pack,
the packed result) PLUS its ``peak_bytes_reserved``, where the v5e's allocator keeps a
running program's temporaries (the fused windowed program's dense operands and
accumulators).  The two peaks need not fall together, so the sum is an upper bound."""


def read(ctx):
    import jax

    peaks = [
        int(st.get("peak_bytes_in_use", 0))
        + int(st.get("peak_bytes_reserved", 0))
        for st in ((d.memory_stats() or {}) for d in jax.devices())
    ]
    return max(peaks) / 1e9 if peaks and max(peaks) else None
