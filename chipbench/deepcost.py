"""The least bytes a breadth-first search must move, from the
REFERENCE's counts: computed, not measured, and blind to how the
program runs its levels (``cost.py``'s convention: a lower bound, so the
share of the roofline it gives is an upper bound).

Assumed: a lane reads every directed edge of its root's component once
(4 B, the neighbour's id: in a level-synchronous search every edge of
the component leaves the frontier exactly once) and writes each reached
vertex's parent and level once (8 B).  Nothing is charged for finding
the frontier, for the column offsets, for the reads that find a vertex
already visited, or for any access pattern: a sweep that gathers every
slot of the matrix at every level and a walk of the frontier's columns
are held to the same bytes.
"""

from __future__ import annotations

EDGE_BYTES = 4
VERTEX_BYTES = 8


def bfs_search_least_bytes(edges: float, vertices: float) -> float:
    """One search over a component of ``vertices`` reached vertices and
    ``edges`` directed edges (the degree sum over the reached)."""
    return EDGE_BYTES * edges + VERTEX_BYTES * vertices
