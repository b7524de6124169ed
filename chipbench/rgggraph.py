"""The benchmark's second graph law: a random geometric graph.

DIMACS10's family ``rgg_n_2_<k>_s0`` (Holtgrewe, Sanders, Schulz):
``n = 2**k`` points uniform in the unit square, an edge between two
points closer (Euclidean) than ``0.55 * sqrt(ln n / n)``.  Bounded degree
(about ``0.3025 pi ln n``: 13 at k = 20), one giant component and a
diameter of hundreds of hops: the shape of a road network, the input GAP
calls ``road``.  The public files cannot be had here; the generator and
the seed are ours, the law is theirs.

numpy only, like ``graph.py``: nothing here imports the package or JAX.
Vertex ids follow a grid of cells of side >= r, row-major (the locality
the public instances' ids have); pairs are looked for in a cell's 3 x 3
neighbourhood.  ``brute_force`` is the O(n^2) twin the tests hold the
cell search to.

Convention (``graph.py``'s): symmetrised, no loops, no duplicates, COO
sorted by ``rows * n + cols``.
"""

from __future__ import annotations

import numpy as np

RADIUS_FACTOR = 0.55

#: a cell and the half of its 3 x 3 neighbourhood that comes after it
#: row-major: every unordered pair of cells is visited once
_FORWARD = ((0, 0), (1, 0), (-1, 1), (0, 1), (1, 1))


def radius(n: int) -> float:
    """The family's connection radius, ``0.55 * sqrt(ln n / n)``."""
    return RADIUS_FACTOR * float(np.sqrt(np.log(n) / n))


def points(n_log2: int, seed: int):
    """``(pts, side)``: the seeded points, ``float64[n, 2]`` in the unit
    square, in the order of their ids (row-major over a grid of ``side``
    x ``side`` cells, each at least one radius wide; a stable sort, so
    the order inside a cell is the draw's), and the grid's side."""
    n = 1 << n_log2
    pts = np.random.default_rng(seed).random((n, 2))
    side = max(int(np.floor(1.0 / radius(n))), 1)
    order = np.argsort(_cell_of(pts, side), kind="stable")
    return pts[order], side


def _cell_of(pts, side: int):
    xy = np.minimum((pts * side).astype(np.int64), side - 1)
    return xy[:, 1] * side + xy[:, 0]


def _finish(n: int, lo, hi):
    """Unordered pairs ``lo < hi`` -> ``(n, rows, cols, keys)``."""
    keys = np.sort(np.concatenate([lo * n + hi, hi * n + lo]))
    return n, (keys // n).astype(np.int32), (keys % n).astype(np.int32), keys


def rgg_graph(n_log2: int, seed: int):
    """The family's graph at ``n = 2**n_log2`` from ``seed``.  Returns
    ``(n, rows, cols, keys)`` as ``graph.rmat_graph`` does: int32
    ``rows`` / ``cols`` sorted by ``keys = rows * n + cols`` (int64,
    unique, ascending)."""
    pts, side = points(n_log2, seed)
    n = len(pts)
    r2 = radius(n) ** 2
    cell = _cell_of(pts, side)  # ascending: the ids follow the cells
    start = np.searchsorted(cell, np.arange(side * side + 1))
    count = np.diff(start)
    cx, cy = cell % side, cell // side
    ids = np.arange(n, dtype=np.int64)
    los, his = [], []
    for dx, dy in _FORWARD:
        ox, oy = cx + dx, cy + dy
        inside = (ox >= 0) & (ox < side) & (oy < side)
        other = np.where(inside, oy * side + ox, 0)
        # every point against every point of the other cell
        width = np.where(inside, count[other], 0)
        i = np.repeat(ids, width)
        first = np.cumsum(width) - width
        j = np.repeat(start[other], width) + (
            np.arange(len(i), dtype=np.int64) - np.repeat(first, width))
        d = pts[i] - pts[j]
        near = (d * d).sum(axis=1) < r2
        if (dx, dy) == (0, 0):
            near &= i < j
        i, j = i[near], j[near]
        los.append(np.minimum(i, j))
        his.append(np.maximum(i, j))
    return _finish(n, np.concatenate(los), np.concatenate(his))


def brute_force(n_log2: int, seed: int):
    """The same graph by every pair's distance, O(n^2): the twin a test
    holds ``rgg_graph`` to at a small size."""
    pts, _ = points(n_log2, seed)
    n = len(pts)
    d = pts[:, None, :] - pts[None, :, :]
    lo, hi = np.nonzero(np.triu((d * d).sum(axis=2) < radius(n) ** 2, k=1))
    return _finish(n, lo.astype(np.int64), hi.astype(np.int64))
