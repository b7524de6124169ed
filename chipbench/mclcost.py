"""The bytes one clustering job must move.  Computed from the
reference's counts, as ``sqcost.py``'s: a LOWER bound whatever
implements the job, so the share of the roofline it gives is an upper
bound on how close the program is."""

from __future__ import annotations

#: a stored entry as the program holds it: two int32 ids and one
#: float32 value
ENTRY_BYTES = 12


def mcl_job_least_bytes(nnz_start: int, stored) -> int:
    """One job: every iteration reads its operand twice (the left and
    the right of ``A @ A``: a perfect cache, every entry fetched once an
    operand) and writes what it keeps, 12 B an entry.  ``nnz_start``:
    the stored entries of the matrix the first iteration expands (the
    input's nonzeros and the loops); ``stored``: the entries kept after
    every iteration's select, the next one's operand.  It charges
    NOTHING to what an implementation adds: a dense state, the unpruned
    product, the passes of a select, the interpretation.  The least
    work of the job, not of any kernel: a share of the roofline
    computed from it cannot pass 100%."""
    stored = [int(s) for s in stored]
    operands = [int(nnz_start)] + stored[:-1]
    return ENTRY_BYTES * sum(2 * a + c for a, c in zip(operands, stored))


def dense_flop_share(dense_flops: float, device_s: float,
                     peak_tflops: float) -> float:
    """What the dense iterations' products issue (the program's own
    count: two flop a cell of the padded state's contraction, times the
    passes of the input mode) over the matrix unit's peak, for the
    device time of one job (%).  Logged beside the by-scope table; no
    metric: the implementation's work, not the job's."""
    return 100.0 * dense_flops / (peak_tflops * 1e12) / device_s
