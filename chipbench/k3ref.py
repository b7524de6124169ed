"""The plain reference of Graph500 kernel 3 (single-source shortest
paths): exact distances and the specification's validation rules.

numpy / scipy only: nothing here imports the package or JAX, so the
comparison that decides ``correct`` cannot move with the program.  The
graph and its weights are ``graph.py``'s (``rmat_graph``,
``edge_weights``: symmetric multiples of 1/256 in (0, 1]), so every path
sum is exact in float32 and float64 alike and every comparison below is
an equality.  A weight may be zero (the specification draws from [0, 1)):
rule 5 is checked by following the parents, not by the distance falling
along them, which a zero-weight tree edge does not make it do.

Convention (the package's): a COO entry ``(r, c)`` is the edge
``c -> r``; the graph is symmetric.
"""

from __future__ import annotations

import numpy as np

#: the specification's rules, as ``check_tree`` names the first one broken
RULES = {
    1: "the root is its own parent at distance 0",
    2: "every other reached vertex has a parent, (parent, v) is an input "
       "edge and d[v] == d[parent] + w(parent, v)",
    3: "for every input edge |d[u] - d[v]| <= w(u, v), and both ends are "
       "reached or neither",
    4: "an unreached vertex has distance +inf and no parent",
    5: "following parents from a reached vertex ends at the root",
}


class K3Reference:
    """One weighted graph: scipy CSR built once (the COO is sorted by
    row, so the CSR needs no sort), then per-root checks."""

    def __init__(self, n: int, rows, cols, weights):
        import scipy.sparse as sp

        self.n = int(n)
        self.rows = np.asarray(rows)
        self.cols = np.asarray(cols)
        self.w = np.asarray(weights, np.float64)
        if len(self.w) and not np.all(self.w >= 0):
            raise ValueError("kernel-3 reference: a weight is negative")
        self.keys = self.rows.astype(np.int64) * self.n + self.cols
        if np.any(np.diff(self.keys) <= 0):
            raise ValueError(
                "kernel-3 reference: COO not sorted by (row, col), or an "
                "edge is given twice"
            )
        indptr = np.searchsorted(
            self.rows, np.arange(self.n + 1, dtype=np.int64)
        ).astype(np.int64)
        self.deg = np.diff(indptr)
        # symmetric graph: row r's entries are r's neighbours either way
        self.G = sp.csr_matrix(
            (self.w, self.cols, indptr), shape=(self.n, self.n)
        )

    def distances(self, root: int):
        """float64 distances from ``root`` (+inf unreachable)."""
        from scipy.sparse import csgraph

        return csgraph.dijkstra(self.G, indices=int(root))

    def check_exact(self, dist, root: int) -> str | None:
        want = self.distances(root)
        got = np.asarray(dist).astype(np.float64)
        if not np.array_equal(got, want):
            bad = int(np.flatnonzero(got != want)[0])
            return (f"root {root}: dist[{bad}] = {float(got[bad])}, reference "
                    f"says {float(want[bad])}")
        return None

    def check_tree(self, dist, parents, root: int) -> str | None:
        """The five rules against the answer's own arrays and every
        edge.  Together they make ``dist`` the shortest distances and
        ``parents`` a shortest-path tree, whichever of several equal
        parents was picked.  Returns the first broken rule, named, or
        None."""
        d = np.asarray(dist).astype(np.float64)
        p = np.asarray(parents).astype(np.int64)
        n, root = self.n, int(root)

        def broken(rule: int, what: str) -> str:
            return f"root {root}: rule {rule} ({RULES[rule]}): {what}"

        def first(among, mask) -> int | None:
            """The first vertex of ``among`` where ``mask`` holds."""
            return int(among[np.argmax(mask)]) if mask.any() else None

        if d[root] != 0 or p[root] != root:
            return broken(1, f"d = {float(d[root])}, parent = {int(p[root])}")
        reached = np.isfinite(d)
        out = np.flatnonzero(~reached)
        bad = first(out, (d[out] != np.inf) | (p[out] >= 0))
        if bad is not None:
            return broken(4, f"vertex {bad}: d = {float(d[bad])}, parent = "
                             f"{int(p[bad])}")
        v = np.flatnonzero(reached)
        v = v[v != root]
        pv = p[v]
        bad = first(v, (pv < 0) | (pv >= n))
        if bad is not None:
            return broken(2, f"vertex {bad} has parent {int(p[bad])}")
        key = v * np.int64(n) + pv  # entry (v, p) is the edge p -> v
        pos = np.minimum(np.searchsorted(self.keys, key),
                         max(len(self.keys) - 1, 0))
        bad = first(v, self.keys[pos] != key if len(self.keys)
                    else np.ones(len(v), bool))
        if bad is not None:
            return broken(2, f"({int(p[bad])}, {bad}) is not an edge")
        # before rule 2's equation, which a cycle of positive weights
        # breaks too: a parent cycle is then named for what it is.
        # Pointer doubling: after k steps ``up`` is 2^k parents up, and
        # the root and the unreached stay where they are
        up = np.arange(n, dtype=np.int64)
        up[v] = pv
        for _ in range(max(n - 1, 1).bit_length()):
            up = up[up]
        bad = first(v, up[v] != root)
        if bad is not None:
            return broken(5, f"the parents of {bad} lead to {int(up[bad])} "
                             "and stay there")
        bad = first(v, d[pv] + self.w[pos] != d[v])
        if bad is not None:
            return broken(2, f"d[{bad}] = {float(d[bad])} is not "
                             f"d[{int(p[bad])}] + w")
        # every edge (rows are sorted, so the row side is a repeat)
        dr, dc = np.repeat(d, self.deg), d[self.cols]
        fr, fc = np.isfinite(dr), np.isfinite(dc)
        if np.any(fr != fc):
            return broken(3, "an edge leaves the reached set")
        both = fr & fc
        if np.any(np.abs(dr[both] - dc[both]) > self.w[both]):
            return broken(3, "an edge is shorter than the difference of "
                             "its ends' distances")
        return None
