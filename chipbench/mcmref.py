"""The plain reference of upstream's maximum cardinality matching
(``BPMaximumMatching.cpp``): scipy's Hopcroft-Karp for the cardinality
and a numpy check that what was returned IS a matching of the pattern
(numpy / scipy only; nothing from the program).

A maximum matching is not unique, its cardinality is: the check holds
the mates to the three things any maximum matching has (they are each
other's inverse, so no vertex is matched twice; every matched pair is a
stored nonzero, looked up in the sorted COO and not in a dense matrix;
as many pairs as scipy finds), and not to scipy's own mates.  Integers
throughout, so the limits are equality and no precision below the
configuration's exists that could pass by rounding.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph


class McmReference:
    """``cardinality``: the maximum matching's size on the pattern
    ``rows`` / ``cols`` (each nonzero once) of an ``nr`` x ``nc``
    matrix."""

    def __init__(self, nr: int, nc: int, rows, cols):
        self.nr, self.nc = int(nr), int(nc)
        rows, cols = np.asarray(rows, np.int64), np.asarray(cols, np.int64)
        self.keys = np.sort(rows * self.nc + cols)  # the stored nonzeros
        self.nnz = len(self.keys)
        adj = sp.csr_matrix(
            (np.ones(self.nnz, np.int8), (rows, cols)),
            shape=(self.nr, self.nc))
        mate = csgraph.maximum_bipartite_matching(adj, perm_type="column")
        self.cardinality = int((mate >= 0).sum())

    def is_edge(self, r, c) -> np.ndarray:
        """bool, pair by pair: ``(r, c)`` is a stored nonzero."""
        k = np.asarray(r, np.int64) * self.nc + np.asarray(c, np.int64)
        at = np.searchsorted(self.keys, k)
        return self.keys[np.minimum(at, self.nnz - 1)] == k

    def check(self, mate_row, mate_col) -> str | None:
        """None where ``(mate_row, mate_col)`` is a maximum matching of
        the pattern, else the first thing that is wrong."""
        mr, mc = np.asarray(mate_row), np.asarray(mate_col)
        for name, m, n, other in (("mate_row", mr, self.nr, self.nc),
                                  ("mate_col", mc, self.nc, self.nr)):
            if m.shape != (n,) or m.dtype.kind != "i":
                return (f"{name} is {m.dtype}{list(m.shape)}, not one "
                        f"integer a vertex of {n}")
            if m.min(initial=0) < -1 or m.max(initial=-1) >= other:
                return f"{name} holds a mate outside -1 .. {other - 1}"
        rs = np.flatnonzero(mr >= 0)
        cs = np.flatnonzero(mc >= 0)
        # each other's inverse: then no vertex is matched twice
        back = np.flatnonzero(mc[mr[rs]] != rs)
        if len(back):
            r = int(rs[back[0]])
            return (f"{len(back)} matched rows are not their column's "
                    f"mate: row {r} has column {int(mr[r])}, whose mate "
                    f"is {int(mc[mr[r]])}")
        forth = np.flatnonzero(mr[mc[cs]] != cs)
        if len(forth):
            c = int(cs[forth[0]])
            return (f"{len(forth)} matched columns are not their row's "
                    f"mate: column {c} has row {int(mc[c])}, whose mate "
                    f"is {int(mr[mc[c]])}")
        stray = np.flatnonzero(~self.is_edge(rs, mr[rs]))
        if len(stray):
            r = int(rs[stray[0]])
            return (f"{len(stray)} matched pairs are no stored nonzero: "
                    f"row {r} with column {int(mr[r])}")
        if len(rs) != self.cardinality:
            return (f"{len(rs)} pairs are matched, the maximum is "
                    f"{self.cardinality}")
        return None
