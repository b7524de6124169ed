"""The bytes one matching job must move.  From the graph alone, so that
it reads the same work whatever implements it: a LOWER bound, and the
share of the roofline it gives an upper bound on how close the program
is."""

from __future__ import annotations


def mcm_job_least_bytes(nnz: int, nr: int, nc: int) -> int:
    """Every stored nonzero's index read once (4 B: no matching can be
    called maximum without having looked at every edge) and both mate
    vectors written once (int32).  Nothing is charged for a second look
    at any edge (a phase's layers, a round's proposals), for the parents,
    the chase or the winner selection, nor for any access pattern: what
    the measured time is expected to be dominated by."""
    return 4 * (nnz + nr + nc)
