"""The benchmark's own graph generator and plain references.

numpy / scipy only: nothing here imports the package or JAX, so the
inputs and the comparison that decides ``correct`` cannot move with the
program.  Copied from ``chip_smoke.py`` (``edge_weights``,
``ref_bfs_levels``, ``check_tree``) and ``utils/rmat.py``'s host
generator; the generator draws one float32 uniform per level instead of
three float64 ones (same quadrant law, about 6x less host time).

Convention (the package's): a COO entry ``(r, c)`` is the edge
``c -> r``.  The graphs here are symmetric, so direction never matters
to BFS levels, only to how a parent edge is looked up.
"""

from __future__ import annotations

import numpy as np

RMAT_A, RMAT_B, RMAT_C = 0.57, 0.19, 0.19


def rmat_graph(scale: int, edgefactor: int, seed: int):
    """Graph500 kernel-2 input: R-MAT (A=.57, B=C=.19, D=.05),
    ``edgefactor * 2**scale`` edges, vertices relabelled by a seeded
    permutation, symmetrised, de-looped, deduplicated.

    Returns ``(n, rows, cols, keys)``: int32 ``rows`` / ``cols`` sorted by
    ``keys = rows * n + cols`` (int64, unique, ascending).
    """
    rng = np.random.default_rng(seed)
    n = 1 << scale
    nedges = edgefactor * n
    ab = np.float32(RMAT_A + RMAT_B)
    # P(dst bit = 1 | src bit): b/(a+b) on the upper half, d/(c+d) below
    p_up = np.float32(RMAT_B / (RMAT_A + RMAT_B))
    p_lo = np.float32((1.0 - RMAT_A - RMAT_B - RMAT_C)
                      / (1.0 - RMAT_A - RMAT_B))
    src = np.zeros(nedges, np.int64)
    dst = np.zeros(nedges, np.int64)
    for level in range(scale):
        u = rng.random(nedges, dtype=np.float32)
        src_bit = u >= ab
        # one uniform decides both bits: rescale it inside its half
        v = np.where(src_bit, (u - ab) / (np.float32(1.0) - ab), u / ab)
        dst_bit = v < np.where(src_bit, p_lo, p_up)
        src |= src_bit.astype(np.int64) << level
        dst |= dst_bit.astype(np.int64) << level
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    keys = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    rows = (keys // n).astype(np.int32)
    cols = (keys % n).astype(np.int32)
    return n, rows, cols, keys


def edge_weights(rows, cols, seed: int):
    """Seeded weights in (0, 1], symmetric in (i, j), multiples of 1/256
    (path sums are then exact in f32 and f64 alike)."""
    lo = np.minimum(rows, cols).astype(np.uint64)
    hi = np.maximum(rows, cols).astype(np.uint64)
    h = lo * np.uint64(0x9E3779B97F4A7C15) + hi * np.uint64(
        0xC2B2AE3D27D4EB4F
    ) + np.uint64(seed)
    h ^= h >> np.uint64(29)
    h *= np.uint64(0xBF58476D1CE4E5B9)
    h ^= h >> np.uint64(32)
    return ((h % np.uint64(255)) + np.uint64(1)).astype(np.float32) / 256.0


def degrees(rows, n: int):
    return np.bincount(rows, minlength=n).astype(np.int64)


def draw_roots(deg, seed: int, count: int):
    """``count`` roots uniform over vertices of degree > 0, drawn with
    replacement from the seed (every request gets a fresh draw)."""
    rng = np.random.default_rng([seed, 0x7007])
    live = np.flatnonzero(deg > 0)
    return live[rng.integers(0, len(live), count)].astype(np.int32)


class Reference:
    """The plain reference over one graph: scipy CSR built once (the COO
    is sorted by row, so the CSR needs no sort), then per-root checks."""

    def __init__(self, n: int, rows, cols, keys=None):
        import scipy.sparse as sp

        self.n = int(n)
        self.rows = np.asarray(rows)
        self.cols = np.asarray(cols)
        self.keys = (
            self.rows.astype(np.int64) * n + self.cols
            if keys is None else keys
        )
        indptr = np.searchsorted(
            self.rows, np.arange(n + 1, dtype=np.int64)
        ).astype(np.int64)
        # symmetric graph: row r's entries are r's neighbours either way
        self.G = sp.csr_matrix(
            (np.ones(len(self.cols), np.float32), self.cols, indptr),
            shape=(n, n),
        )
        self.deg = degrees(self.rows, n)

    def bfs_levels(self, root: int):
        """Hop counts from ``root`` (-1 unreachable): scipy dijkstra with
        every edge counted as 1."""
        from scipy.sparse import csgraph

        d = csgraph.dijkstra(self.G, indices=int(root), unweighted=True)
        return np.where(np.isfinite(d), d, -1).astype(np.int32)

    def traversed_edges(self, levels) -> int:
        """Graph500 kernel-2 edge count of one search: input edges inside
        the traversed component = half the degree sum over reached
        vertices (``batch_traversed_edges``' definition)."""
        return int(self.deg[np.asarray(levels) >= 0].sum()) // 2

    def check_exact(self, levels, root: int) -> str | None:
        want = self.bfs_levels(root)
        if not np.array_equal(np.asarray(levels).astype(np.int32), want):
            bad = int(np.flatnonzero(np.asarray(levels) != want)[0])
            return (f"root {root}: level[{bad}] = {int(levels[bad])}, "
                    f"reference says {int(want[bad])}")
        return None

    def check_tree(self, levels, parents, root: int) -> str | None:
        """The Graph500 validation rules, against the answer's own
        levels and all edges: the root is its own parent at level 0;
        every other reached vertex's parent sits one level up and
        (parent, v) is an edge; every edge joins two reached vertices at
        most one level apart or two unreached ones; unreached vertices
        have no parent.  Together these make the levels the BFS levels.
        Returns the first broken rule, or None."""
        levels = np.asarray(levels).astype(np.int64)
        parents = np.asarray(parents).astype(np.int64)
        n, root = self.n, int(root)
        reached = levels >= 0
        if levels[root] != 0 or parents[root] != root:
            return f"root {root}: not its own parent at level 0"
        if np.any(parents[~reached] >= 0):
            return f"root {root}: an unreached vertex has a parent"
        v = np.flatnonzero(reached)
        v = v[v != root]
        p = parents[v]
        if np.any((p < 0) | (p >= n)):
            return f"root {root}: a reached vertex has no parent"
        if np.any(levels[p] != levels[v] - 1):
            bad = int(v[np.flatnonzero(levels[p] != levels[v] - 1)[0]])
            return (f"root {root}: level[parent[{bad}]] != "
                    f"level[{bad}] - 1")
        key = v * np.int64(n) + p  # entry (v, p) is the edge p -> v
        pos = np.minimum(np.searchsorted(self.keys, key),
                         len(self.keys) - 1)
        if np.any(self.keys[pos] != key):
            bad = int(v[np.flatnonzero(self.keys[pos] != key)[0]])
            return f"root {root}: (parent[{bad}], {bad}) is not an edge"
        # every edge, in one byte a level (the table then sits in cache;
        # rows are sorted, so the row side is a repeat, not a gather)
        lv8 = np.clip(levels, -1, 126).astype(np.int8)
        lr, lc = np.repeat(lv8, self.deg), lv8[self.cols]
        if np.any((lr >= 0) != (lc >= 0)):
            return f"root {root}: an edge leaves the reached set"
        if np.any(np.abs(lr - lc) > 1):
            return f"root {root}: an edge spans more than one level"
        return None
