"""The bytes one triangle-count job must move.  Computed from shapes and
the job's own counts, as ``cost.py``'s: a LOWER bound whatever
implements the count over a bit-packed adjacency, so the share of the
roofline it gives is an upper bound on how close the program is."""

from __future__ import annotations


def tc_job_least_bytes(n: int, nonzeros: int) -> int:
    """One job of ``models/tc.py:tc_job`` on an ``n``-vertex graph of
    ``nonzeros`` stored entries: the ``n * n / 8``-byte table written
    once (the pack) and read once (a perfect cache: every row fetched
    once, not once per pair that names it), and the edge list, 8 B a
    stored nonzero (two int32 ids), read twice (the sort that opens the
    job, the walk of the pairs).  It does NOT charge the rows a pair
    re-fetches (``gathered_bytes`` below is what today's scan moves): a
    kernel that shares a row across its pairs would then read over 100%.
    Nothing is charged for the sorts' passes, the zero fill or the
    scatter's real access pattern."""
    return 2 * (n * n // 8) + 2 * 8 * nonzeros


def gathered_bytes(pairs: int, n: int) -> int:
    """What the scan of ``popcount_pair_counts`` gathers: two rows of
    ``n / 8`` bytes a pair slot walked, kept or not.  Logged beside the
    share as a rate (bytes over ``tc_harvest_ms``); no metric."""
    return pairs * 2 * (n // 8)
