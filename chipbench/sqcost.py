"""The bytes one sparse product must move.  Computed from the counts of
the operands and of the answer, as ``cost.py``'s: a LOWER bound whatever
implements the product, so the share of the roofline it gives is an
upper bound on how close the program is."""

from __future__ import annotations

#: a stored entry as the program holds it: two int32 ids and one
#: float32 value
ENTRY_BYTES = 12


def sq_job_least_bytes(nnz_a: int, nnz_c: int) -> int:
    """One job ``C = A @ A``: A read twice (once as the left operand,
    once as the right: a perfect cache, every entry fetched once an
    operand and not once a product it joins) and C written once, 12 B
    an entry.  It charges NOTHING to what an implementation adds: dense
    operands, a dense product, the passes of an extraction or of a
    sort.  The least work of the job, not of any kernel: a share of
    the roofline computed from it cannot pass 100% however the product
    is made."""
    return ENTRY_BYTES * (2 * nnz_a + nnz_c)


def dense_flop_share(dense_flops: float, device_s: float,
                     peak_tflops: float) -> float:
    """What the dense stage products issue (the program's own count:
    two flop a cell of every launched window's contraction) over the
    matrix unit's peak, for the device time of one job (%).  Logged
    beside the by-scope table; no metric: the implementation's work,
    not the job's."""
    return 100.0 * dense_flops / (peak_tflops * 1e12) / device_s
