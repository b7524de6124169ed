"""The bytes a served batch of Graph500 kernel 3 must move.  Computed
from shapes, as ``cost.py``'s: a LOWER bound, so the share of the
roofline it gives is an upper bound on how close the program is."""

from __future__ import annotations


def sssp_batch_least_bytes(n: int, slots: int, width: int, rounds: float,
                           ) -> float:
    """The least HBM traffic of one ``width``-wide Bellman-Ford batch
    with its parents pass (``models/sssp.py:_sssp_batch_impl``).

    Assumed: each of ``rounds`` min-plus sweeps reads every padded slot's
    column index and weight once (4 + 4 B a slot), reads the f32
    ``[n, width]`` distances once (a perfect cache: every gathered row is
    fetched once, not once per edge) and writes them once; the parents
    pass reads the slots once and the distances twice (the gather table,
    each row's own) and writes the int32 ``[n, width]`` parents.  Nothing
    is charged for row ids or the gather's real access pattern, which is
    what the measured time is expected to be dominated by.  ``rounds``
    may be a mean over batches."""
    sweep = 8 * slots + 2 * 4 * n * width
    return rounds * sweep + sweep + 4 * n * width
