"""A seeded STAND-IN for a protein similarity network: planted families.

HipMCL's inputs (all-against-all alignment scores of 10^5 to 10^8
proteins) cannot be had or held here, so the MCL configuration clusters
a graph with what matters of one: vertices in FAMILIES of very unequal
sizes, most of a vertex's edges inside its family and heavy, a fifth of
them anywhere and light, nothing in the order of the ids.  Everything is
drawn from ``seed`` with numpy alone.

* ``n = 2**scale`` vertices; family sizes drawn from ``P(s) ~
  s**-alpha`` on ``[smin, smax]`` until ``n`` is filled (the last family
  takes what is left; a remainder under ``smin`` joins the family
  before it).
* every vertex draws ``degree`` neighbours, each with probability
  ``inside`` uniformly from its own family and otherwise uniformly from
  all vertices;
* an edge whose ends share a family weighs ``U(w_in)``, any other
  ``U(w_out)`` (similarity scores), rounded ONCE to float32 so that
  program and reference read the same values;
* loops dropped, a pair drawn twice kept once (its first weight),
  symmetrised, and the vertex ids permuted (HipMCL permutes for balance,
  ``-rand``).
"""

from __future__ import annotations

import numpy as np

DEFAULTS = dict(
    degree=128, inside=0.8, smin=8, smax=1024, alpha=1.5,
    w_in=(0.3, 1.0), w_out=(0.05, 0.3),
)


def family_sizes(n: int, rng, smin: int, smax: int, alpha: float):
    """Sizes that sum to ``n``, drawn by inverting the power law's CDF
    on the integers of ``[smin, smax]``."""
    s = np.arange(smin, smax + 1, dtype=np.float64)
    cdf = np.cumsum(s ** -alpha)
    cdf /= cdf[-1]
    sizes, left = [], n
    while left > 0:
        size = smin + int(np.searchsorted(cdf, rng.random()))
        size = min(size, left)
        if size < smin and sizes:
            sizes[-1] += size
        else:
            sizes.append(size)
        left -= size
    return np.asarray(sizes, np.int64)


def family_graph(scale: int, seed: int, **params):
    """``(n, rows, cols, vals, family)``: both directions of every
    undirected edge (``rows``/``cols`` int32, ``vals`` float32), and the
    planted family of every vertex (after the permutation)."""
    p = dict(DEFAULTS, **params)
    n = 1 << scale
    rng = np.random.default_rng([seed, scale])
    sizes = family_sizes(n, rng, int(p["smin"]), int(p["smax"]),
                         float(p["alpha"]))
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    fam = np.repeat(np.arange(len(sizes)), sizes)
    deg = int(p["degree"])
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    own = rng.random(n * deg) < float(p["inside"])
    within = starts[fam[src]] + (
        rng.random(n * deg) * sizes[fam[src]]).astype(np.int64)
    dst = np.where(own, within, rng.integers(0, n, n * deg))
    keep = src != dst
    lo, hi = np.minimum(src, dst)[keep], np.maximum(src, dst)[keep]
    _, first = np.unique(lo * n + hi, return_index=True)
    lo, hi = lo[first], hi[first]
    same = fam[lo] == fam[hi]
    u = rng.random(len(lo))
    (a, b), (c, d) = p["w_in"], p["w_out"]
    w = np.where(same, a + (b - a) * u, c + (d - c) * u).astype(np.float32)
    perm = rng.permutation(n)
    lo, hi = perm[lo], perm[hi]
    family = np.empty(n, np.int64)
    family[perm] = fam
    rows = np.concatenate([lo, hi]).astype(np.int32)
    cols = np.concatenate([hi, lo]).astype(np.int32)
    return n, rows, cols, np.concatenate([w, w]), family
