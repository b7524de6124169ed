"""The bytes one FastSV job must move.  Computed from shapes, as
``cost.py``'s: a LOWER bound, so the share of the roofline it gives is
an upper bound on how close the program is."""

from __future__ import annotations


def cc_round_least_bytes(slots: int, n: int) -> int:
    """One round of ``models/cc.py:_fastsv``: the one-lane sweep reads
    every padded slot's column index once (4 B a slot; the structural
    values are never needed under select2nd) and its ``n + 1`` int32
    gather table once (a perfect cache: every label is fetched once, not
    once per edge); the hook and the two minimums are charged three
    passes over ``n`` int32 labels (``u`` written, ``f`` read, the new
    ``f`` written).  Nothing is charged for ``f[f]``'s own gather, for
    row ids, for the scatter-min's real access pattern or for the
    sweep's, which is what the measured time is expected to be dominated
    by."""
    return 4 * slots + 4 * (n + 1) + 3 * 4 * n


def cc_job_least_bytes(slots: int, n: int, rounds: float, jumps: float,
                       ) -> float:
    """``rounds`` rounds and ``jumps`` iterations of the pointer-jumping
    loop, each of those one read and one write of ``n`` int32 labels.
    The counts may be means over jobs."""
    return rounds * cc_round_least_bytes(slots, n) + jumps * 2 * 4 * n
