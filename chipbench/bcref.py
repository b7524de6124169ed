"""The plain reference of GAP's BC kernel (Brandes' betweenness
centrality, unweighted) for one source at a time, in float64, and the
checks that hold a served answer to it.

numpy / scipy only: nothing here imports the package or JAX, so the
comparison that decides ``correct`` cannot move with the program.  The
exact BFS levels are ``graph.py``'s (scipy's dijkstra with every edge
counted as 1).

What a request returns, and what is compared: Brandes' dependency vector
of ONE source ``s``, ``delta_s(v) = sum over t of sigma_st(v) /
sigma_st`` (``sigma_st``: shortest ``s``-``t`` paths; ``sigma_st(v)``:
those through ``v``), endpoints excluded (``delta_s(s) = 0``, and ``t``
itself is no interior vertex), nothing halved and nothing normalised.
A GAP trial is four sources; its scores are the four vectors added (the
client adds them, and divides by the largest if it wants GAP's
normalised output).

The tolerances, and why none is an equality.  The program counts paths
and accumulates dependencies in float32.  A path count is exact up to
2^24 and rounded beyond (at scale 20 the largest is about 10^4, so the
counts are exact there; a larger or denser graph passes 2^24);
``(1 + delta) / sigma`` is a rounded quotient in any case, dependencies
reach 4 x 10^5 and are no integers; and each of a row's sums is folded
in the ELL sweep's order
(degree class by degree class, then across a hub's bucket rows), not in
numpy's.  Every term of every sum is positive, so nothing cancels and
the error stays RELATIVE: a handful of float32 roundings a level, over
at most ten levels and back.  bfloat16, the chip's next precision down,
keeps 8 bits where float32 keeps 24: the same sums are then wrong in the
third digit (``PERF.md`` section 4 has both readings).  An entry the
reference gives as 0 (the source, an unreached vertex, a vertex no
shortest path passes through) must be 0 exactly: the program adds
nothing there.
"""

from __future__ import annotations

import numpy as np

from chipbench import graph

#: largest relative error of one score, and of a four-source sum of
#: scores, against float64.  THIS is the limit that tells the stated
#: precision from the one below it: float32 reads 6e-7 on the chip and
#: at most 6.3e-5 in numpy's row order, bfloat16 9.4e-3 (readings:
#: PERF.md section 4; ``python3 -m chipbench.bccontrol`` is the count)
RTOL = 2e-4
#: largest relative error of a whole answer's sum against the sum rule's
#: integer: 10^6 positive terms average their roundings out.  The guard
#: of the WHOLE array on every sampled answer (a level dropped, a lane
#: mixed up, a class of rows not swept), at the cost of one BFS; no guard
#: of the precision: bfloat16 passes it by as little as five times
RTOL_SUM = 6e-6


class BCReference:
    """One unweighted symmetric graph: ``graph.Reference``'s CSR in
    float64, then per-source Brandes and the checks."""

    def __init__(self, n: int, rows, cols, bfs: graph.Reference | None = None):
        self.n = int(n)
        self.bfs = graph.Reference(n, rows, cols) if bfs is None else bfs
        # symmetric: row r's entries are r's neighbours either way, so
        # one matrix pulls path counts forward and dependencies back
        self.G = self.bfs.G.astype(np.float64)

    def levels(self, root: int):
        """Exact hop counts from ``root`` (-1 unreachable)."""
        return self.bfs.bfs_levels(root)

    def dependencies(self, root: int, levels=None):
        """``delta_root`` in float64: path counts forward level by
        level, dependencies backward, the root's own entry 0."""
        lv = self.levels(root) if levels is None else np.asarray(levels)
        depth = int(lv.max())
        sigma = np.zeros(self.n)
        sigma[root] = 1.0
        for d in range(1, depth + 1):
            here = lv == d
            sigma[here] = (self.G @ np.where(lv == d - 1, sigma, 0.0))[here]
        delta = np.zeros(self.n)
        for d in range(depth, 0, -1):
            w = np.zeros(self.n)
            here = lv == d
            w[here] = (1.0 + delta[here]) / sigma[here]
            up = lv == d - 1
            delta[up] = sigma[up] * (self.G @ w)[up]
        delta[root] = 0.0
        return delta

    def dependencies_held_in(self, root: int, dtype, levels=None):
        """The control of the precision: the same Brandes with ``sigma``
        and ``delta`` rounded to ``dtype`` wherever they are stored, each
        row's sum still taken in float32 and in numpy's row order.  With
        ``numpy.float32`` it is a legitimate float32 answer in another
        order than the chip's; with bfloat16 it is the answer the limits
        have to refuse."""
        def stored(x):
            return x.astype(dtype).astype(np.float32)

        G = self.bfs.G  # float32 ones
        lv = self.levels(root) if levels is None else np.asarray(levels)
        depth = int(lv.max())
        sigma = np.zeros(self.n, np.float32)
        sigma[root] = 1
        for d in range(1, depth + 1):
            here = lv == d
            sigma[here] = stored(
                (G @ np.where(lv == d - 1, sigma, np.float32(0)))[here])
        delta = np.zeros(self.n, np.float32)
        for d in range(depth, 0, -1):
            here, up = lv == d, lv == d - 1
            w = np.zeros(self.n, np.float32)
            w[here] = (np.float32(1) + delta[here]) / sigma[here]
            delta[up] = stored(sigma[up] * (G @ w)[up])
        delta[root] = 0
        return delta

    def level_count(self, roots) -> int:
        """BFS levels that hold a vertex, the roots' own counted, in the
        deepest of ``roots``: what the served plan returns as its depth."""
        return max(int(self.levels(r).max()) + 1 for r in roots)

    @staticmethod
    def _compare(got, want, rtol: float, what: str) -> str | None:
        got = np.asarray(got).astype(np.float64)
        off = np.abs(got - want) > rtol * want
        if off.any():
            bad = int(np.flatnonzero(off)[0])
            return (f"{what}: score[{bad}] = {float(got[bad])!r}, reference "
                    f"says {float(want[bad])!r} ({int(off.sum())} entries "
                    f"beyond {rtol:g} of the reference)")
        return None

    @staticmethod
    def worst(got, want) -> float:
        """Largest relative error over the entries the reference gives
        as positive (the others are compared exactly)."""
        got = np.asarray(got).astype(np.float64)
        pos = want > 0
        if not pos.any():
            return 0.0
        return float(np.max(np.abs(got[pos] - want[pos]) / want[pos]))

    def check_exact(self, scores, root: int, want=None) -> str | None:
        """Every entry of one answer against float64 Brandes (``want``:
        ``dependencies(root)`` where the caller has it already)."""
        want = self.dependencies(root) if want is None else want
        return self._compare(scores, want, RTOL, f"root {int(root)}")

    def check_trial(self, total, roots, want=None) -> str | None:
        """The sum of one trial's answers against the sum of the
        reference's, entry by entry."""
        if want is None:
            want = sum(self.dependencies(int(r)) for r in roots)
        return self._compare(total, want, RTOL,
                             "trial " + " ".join(str(int(r)) for r in roots))

    def sum_rule(self, root: int, levels=None) -> int:
        """``sum over v of delta_s(v)`` as an integer: every reached
        ``t != s`` has ``d(s, t) - 1`` interior vertices on each of its
        shortest paths, so its paths' shares add up to that."""
        lv = self.levels(root) if levels is None else np.asarray(levels)
        return int(np.sum(lv[lv > 0].astype(np.int64) - 1))

    def check_sum(self, scores, root: int, levels=None) -> str | None:
        """The sum rule on one answer, and its zeros: costs one BFS
        (none where the caller hands the ``levels``) and holds the whole
        array."""
        got = np.asarray(scores).astype(np.float64)
        lv = self.levels(root) if levels is None else np.asarray(levels)
        if np.any(got[lv < 0] != 0.0):
            bad = int(np.flatnonzero((lv < 0) & (got != 0.0))[0])
            return (f"root {int(root)}: vertex {bad} is not reached and "
                    f"scores {float(got[bad])!r}")
        want = self.sum_rule(root, lv)
        total = float(got.sum())
        if abs(total - want) > RTOL_SUM * want:
            return (f"root {int(root)}: the scores add up to {total!r}, the "
                    f"sum rule says {want} (sum over reached t of "
                    "d(s, t) - 1)")
        return None


def check_answer(scores, root: int, deg) -> str | None:
    """What EVERY answer is held to, in O(n) and without a search: the
    root's own score 0, every score finite and not negative, and 0 on
    every vertex without an edge (nearly all a Kronecker graph's
    unreached vertices; ``check_sum`` holds the rest on the sample)."""
    s = np.asarray(scores)
    if float(s[root]) != 0.0:
        return f"root {int(root)} scores {float(s[root])!r} itself"
    if not np.all(np.isfinite(s)) or np.any(s < 0):
        return f"root {int(root)}: a score is negative or not finite"
    if np.any(s[np.asarray(deg) == 0] != 0):
        return f"root {int(root)}: a vertex without an edge has a score"
    return None
