"""The bipartite graph class of upstream's matching driver.

``Applications/BipartiteMatchings/BPMaximumMatching.cpp`` (driver
``bpmm``; Azad & Buluç, IPDPS 2016, section VI) draws its synthetic
inputs itself, from a scale: class ``g500`` is R-MAT with Graph500's
quadrant law (.57 / .19 / .19 / .05).  The matrix is NOT symmetrised:
its rows are one side of the bipartite graph and its columns the other,
a nonzero ``(r, c)`` is the edge between row ``r`` and column ``c``, and
rows and columns are each randomly permuted.  Duplicate nonzeros are
removed; a "loop" ``(i, i)`` is an ordinary edge here (row ``i`` and
column ``i`` are different vertices).

numpy only, like ``graph.py`` (whose quadrant sampler this repeats:
one float32 uniform a level): nothing here imports the package or JAX.
The public driver cannot be had on this machine; the law is theirs, the
generator and the seed are ours.
"""

from __future__ import annotations

import numpy as np

from .graph import RMAT_A, RMAT_B, RMAT_C


def bipartite_rmat(scale: int, edgefactor: int, seed: int):
    """``(nr, nc, rows, cols)``: ``nr = nc = 2**scale``, ``edgefactor *
    2**scale`` R-MAT draws, rows and columns each relabelled by a seeded
    permutation of its own, deduplicated; int32 ``rows`` / ``cols``
    sorted by ``rows * nc + cols``."""
    rng = np.random.default_rng([seed, 0xB1A7])
    n = 1 << scale
    nedges = edgefactor * n
    ab = np.float32(RMAT_A + RMAT_B)
    p_up = np.float32(RMAT_B / (RMAT_A + RMAT_B))
    p_lo = np.float32((1.0 - RMAT_A - RMAT_B - RMAT_C)
                      / (1.0 - RMAT_A - RMAT_B))
    src = np.zeros(nedges, np.int64)
    dst = np.zeros(nedges, np.int64)
    for level in range(scale):
        u = rng.random(nedges, dtype=np.float32)
        src_bit = u >= ab
        v = np.where(src_bit, (u - ab) / (np.float32(1.0) - ab), u / ab)
        dst_bit = v < np.where(src_bit, p_lo, p_up)
        src |= src_bit.astype(np.int64) << level
        dst |= dst_bit.astype(np.int64) << level
    row_perm, col_perm = rng.permutation(n), rng.permutation(n)
    keys = np.unique(row_perm[src] * n + col_perm[dst])
    return n, n, (keys // n).astype(np.int32), (keys % n).astype(np.int32)
