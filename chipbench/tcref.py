"""GAP's TC kernel's plain reference: the triangles of an undirected
graph, each counted once.

numpy / scipy only, nothing from the package: the count that decides
``correct`` cannot move with the program.  The graph is read from a COO
edge list as the deployment holds it (any entry ``(r, c)`` with
``r != c`` is the undirected edge ``{r, c}``; loops and repeated entries
are dropped first, as the program drops them on the device).

The count is ``sum((U @ U) .* U)`` over the DEGREE-ORDERED orientation
``U`` (every edge kept once, pointing from its lower-degree end to its
higher-degree end, ties by vertex id): every triangle has exactly one
vertex that precedes the other two in that order and one that follows
them, so it is found once, as the wedge first -> middle -> last closed
by the edge first -> last.  A vertex's out-degree under the order is at
most ``sqrt(2 m)``, which is what keeps ``U @ U`` small on a skewed
graph (unordered, ``L @ L`` is billions of entries at scale 18: a hub's
row times a hub's row).  The product is taken in row blocks and never
held whole; sums are int64.

Integers, so the limit of the comparison is EQUALITY: there is no
tolerance to choose and no lower precision of an exact count but a
wrong one (``tccontrol.py`` tries three).
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

#: rows of ``U`` a block of the product takes
BLOCK = 1 << 14


def undirected_edges(n: int, rows, cols) -> tuple[np.ndarray, np.ndarray]:
    """``(lo, hi)`` of every undirected edge once, ``lo < hi``: loops
    dropped, both directions and repeats folded."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    keep = rows != cols
    lo = np.minimum(rows, cols)[keep]
    hi = np.maximum(rows, cols)[keep]
    keys = np.unique(lo * n + hi)
    return keys // n, keys % n


def brute_force(n: int, rows, cols) -> int:
    """``trace(A^3) / 6`` on the dense 0/1 adjacency: the definition
    (every triangle is six closed walks of length three), for the small
    graphs that tie ``TCReference`` to it."""
    if n > 512:
        raise ValueError(f"brute force is for n <= 512, got {n}")
    lo, hi = undirected_edges(n, rows, cols)
    a = np.zeros((n, n), np.int64)
    a[lo, hi] = a[hi, lo] = 1
    walks = int(np.trace(a @ a @ a))
    assert walks % 6 == 0
    return walks // 6


class TCReference:
    """The graph's undirected edge count and its triangle count."""

    def __init__(self, n: int, rows, cols, block: int = BLOCK):
        self.n = int(n)
        lo, hi = undirected_edges(n, rows, cols)
        self.edges = int(len(lo))
        deg = np.bincount(lo, minlength=n) + np.bincount(hi, minlength=n)
        # rank of a vertex under (degree, id)
        rank = np.empty(n, np.int64)
        rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
        forward = rank[lo] < rank[hi]
        src = np.where(forward, lo, hi)
        dst = np.where(forward, hi, lo)
        u = sp.csr_matrix(
            (np.ones(len(src), np.int64), (src, dst)), shape=(n, n))
        self.max_out_degree = int(np.diff(u.indptr).max(initial=0))
        total = 0
        for r0 in range(0, n, block):
            blk = u[r0:r0 + block]
            if blk.nnz:
                total += int((blk @ u).multiply(blk).sum())
        self.triangles = total

    def check_count(self, triangles, pairs, edges) -> str | None:
        """None when a job's triple is this graph's: the count EQUAL to
        the reference's, ``edges`` equal to the undirected edge count,
        ``pairs`` (row pairs the harvest walked) at least ``edges``.
        Otherwise what differs."""
        triple = (triangles, pairs, edges)
        if not all(isinstance(v, (int, np.integer)) for v in triple):
            return f"the job's triple {triple!r} is not three integers"
        bad = []
        if int(triangles) != self.triangles:
            bad.append(
                f"{int(triangles)} triangles, the reference counts "
                f"{self.triangles} (off by {int(triangles) - self.triangles})")
        if int(edges) != self.edges:
            bad.append(
                f"{int(edges)} edges of weight 1, the graph has "
                f"{self.edges} undirected edges")
        if int(pairs) < int(edges):
            bad.append(f"{int(pairs)} pairs walked for {int(edges)} edges")
        return "; ".join(bad) or None
