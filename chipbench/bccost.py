"""The bytes a served batch of Brandes' betweenness centrality must
move.  Computed from shapes, as ``cost.py``'s: a LOWER bound, so the
share of the roofline it gives is an upper bound on how close the
program is."""

from __future__ import annotations


def bc_sweep_least_bytes(nnz_slots: int, n: int, W: int) -> int:
    """One plus-times sweep over the f32 ELL matrix: every padded slot's
    column index and value once (4 + 4 B a slot), the f32 ``[n, W]``
    table read once (a perfect cache: every gathered row is fetched
    once, not once per edge) and the f32 ``[n, W]`` result written
    once."""
    return 8 * nnz_slots + 2 * 4 * n * W


def bc_batch_least_bytes(nnz_slots: int, n: int, W: int, forward: float,
                         backward: float) -> float:
    """The least HBM traffic of one ``W``-wide batch of
    ``models/bc.py:_bc_batch_lanes``: ``forward`` path-counting sweeps
    and ``backward`` dependency sweeps, each a whole sweep of the same
    matrix.  Nothing is charged for the elementwise passes over the
    ``[n, W]`` state (levels, path counts, dependencies) between sweeps,
    for row ids, or for the gather's real access pattern, which is what
    the measured time is expected to be dominated by.  The counts may be
    means over batches."""
    return (forward + backward) * bc_sweep_least_bytes(nnz_slots, n, W)
