"""The plain reference of the MCL cell: upstream's loop
(``Applications/MCL.cpp:564-627``) in scipy CSC and float64, no kernels.

    loops set to 1, columns scaled to sum 1
    repeat: expand (A @ A, a block of columns at a time so that it fits)
            MCLPruneRecoverySelect a column (ParFriends.h:186-350)
            columns scaled to sum 1; chaos; inflate (power, rescale)
    until chaos < eps or max_iters
    entries under the prune limit dropped; the clusters are the
    connected components of the symmetrised matrix, each labelled by its
    smallest vertex

Departures from ``MCL.cpp``, each on purpose:

* float64 throughout, where upstream's ``NT`` is whatever it was built
  with (double in its tests): the reference is the precision ABOVE the
  program's float32;
* the select is a plain sort a column, and ties at a threshold are KEPT
  (a threshold's semantics, as ``SpParMat::Kselect1`` +
  ``PruneColumn`` give; upstream's recovery likewise re-selects by
  threshold);
* ``eps`` is the caller's (the configuration's 1e-3: upstream's EPS
  1e-4 is for double, ``models/mcl.py:mcl``'s docstring says why) and
  so is ``max_iters``;
* one process, no phases, no layers: those change no value.

``operand`` rounds the inputs of every product (``mclcontrol.py`` runs
the same loop one precision down); None here.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import connected_components

#: columns of the product computed at a time
BLOCK = 2048
#: the keyword arguments a configuration's ``mcl`` group gives the
#: reference (and, with ``mode``, the program)
PARAMS = ("inflation", "select", "recover", "recover_pct", "prune", "eps",
          "max_iters")


def col_stochastic(A: sp.csc_matrix) -> sp.csc_matrix:
    s = np.asarray(A.sum(axis=0)).ravel()
    return (A @ sp.diags(1.0 / np.where(s != 0, s, 1.0))).tocsc()


def chaos(A: sp.csc_matrix) -> float:
    """max over columns of nnz * (max - sum of squares), the matrix
    column-stochastic (``MCL.cpp:408-422``)."""
    nnz = np.diff(A.indptr)
    ssq = np.asarray(A.multiply(A).sum(axis=0)).ravel()
    mx = A.max(axis=0).toarray().ravel()
    return float(np.max(np.where(nnz > 0, (mx - ssq) * nnz, 0.0)))


def select_column(v, select, recover, recover_pct):
    """The kept mask of one column's candidates ``v`` (already above the
    prune limit), and whether it recovered."""
    if len(v) <= select:
        return np.ones(len(v), bool), False
    s = np.sort(v)[::-1]
    th = s[select - 1]
    recovered = v[v >= th].sum() < recover_pct * v.sum()
    if recovered:
        th = min(th, s[recover - 1] if len(v) >= recover else -np.inf)
    return v >= th, recovered


def mcl_reference(n, rows, cols, vals, *, inflation=2.0, select=1100,
                  recover=1400, recover_pct=0.9, prune=1e-4, eps=1e-3,
                  max_iters=64, keep=(), columns=None, operand=None,
                  log=None):
    """Returns a dict: ``labels`` (int64[n], the smallest vertex of
    every vertex's cluster), ``iters``, ``chaos`` and ``stored`` (one an
    iteration), ``counts`` (an iteration: ``products`` the scalar
    multiplies, ``unpruned`` the product's entries, ``candidates`` those
    above the prune limit, ``bound`` the columns ``select`` cuts,
    ``recovered`` those that recover), ``clusters``, and ``matrices``:
    ``{k: csc}`` the column-stochastic matrix after iteration ``k`` for
    every ``k`` of ``keep``, cut to ``columns`` where given (an ``[n,
    len(columns)]`` matrix, its columns in that order)."""
    A = sp.csc_matrix(
        (np.asarray(vals, np.float64), (rows, cols)), shape=(n, n))
    A.setdiag(1.0)
    A = col_stochastic(A.tocsc())
    out = {"chaos": [], "stored": [], "counts": [], "matrices": {}}
    for it in range(1, max_iters + 1):
        A.sort_indices()
        colcnt, rowcnt = np.diff(A.indptr), np.bincount(A.indices, minlength=n)
        counts = dict(products=int(np.dot(colcnt.astype(np.int64), rowcnt)),
                      unpruned=0, candidates=0, bound=0, recovered=0)
        L = A if operand is None else operand(A)
        parts = []
        for lo in range(0, n, BLOCK):
            C = (L @ L[:, lo:lo + BLOCK]).tocsc()
            counts["unpruned"] += C.nnz
            C.data[C.data < prune] = 0.0
            C.eliminate_zeros()
            counts["candidates"] += C.nnz
            for j in np.flatnonzero(np.diff(C.indptr) > select):
                a, b = C.indptr[j], C.indptr[j + 1]
                kept, rec = select_column(
                    C.data[a:b], select, recover, recover_pct)
                C.data[a:b][~kept] = 0.0
                counts["bound"] += 1
                counts["recovered"] += int(rec)
            C.eliminate_zeros()
            parts.append(C)
        C = col_stochastic(sp.hstack(parts, format="csc"))
        ch = chaos(C)
        C.data **= inflation
        A = col_stochastic(C)
        out["chaos"].append(ch)
        out["stored"].append(int(A.nnz))
        out["counts"].append(counts)
        if it in keep:
            out["matrices"][it] = (
                A.copy() if columns is None else A[:, columns].tocsc())
        if log:
            log(f"mclref: iteration {it}: chaos {ch:.6g}, {A.nnz} stored, "
                f"{counts}")
        if ch < eps:
            break
    A.data[A.data < prune] = 0.0
    A.eliminate_zeros()
    _, comp = connected_components(A + A.T, directed=False)
    first = np.full(comp.max() + 1, n, np.int64)
    np.minimum.at(first, comp, np.arange(n))
    out.update(labels=first[comp], iters=len(out["chaos"]),
               clusters=int(comp.max() + 1))
    return out


# --------------------------------------------------------------------------
# what decides ``correct``
# --------------------------------------------------------------------------

#: ``h(v) = (v + 1) * 0x9E3779B1 mod 2^32``: the program's digest hash
#: (``parallel/spgemm.py:DIGEST_MULTIPLIER``), written out here so that
#: the reference imports nothing of the program
MULTIPLIER = 0x9E3779B1


def fingerprint(labels) -> int:
    """``sum_v labels[v] * h(v)`` mod 2^32."""
    v = np.arange(len(labels), dtype=np.uint64)
    h = ((v + 1) * MULTIPLIER) & 0xFFFFFFFF
    return int((np.asarray(labels, np.uint64) * h).sum() & 0xFFFFFFFF)


def column_distances(n, got, want: sp.csc_matrix, columns) -> np.ndarray:
    """The L1 distance a column between ``got`` (host ``(rows, cols,
    vals)`` of the program's whole matrix) and ``want``, the reference's
    matrix cut to ``columns`` (``[n, len(columns)]``): an entry swapped
    at a threshold costs its own mass."""
    G = sp.csc_matrix(
        (np.asarray(got[2], np.float64), (got[0], got[1])), shape=(n, n))
    D = (G[:, columns] - want).tocsc()
    return np.asarray(abs(D).sum(axis=0)).ravel()


def check_digest(ref: dict, digest: dict, limits: dict) -> str | None:
    """A job's digest against the reference's: iteration count, cluster
    count and label fingerprint by equality; chaos and stored entries an
    iteration within ``limits`` (``chaos_rel`` of the reference's value
    plus ``chaos_abs``; ``stored_rel`` of its count)."""
    if digest["iters"] != ref["iters"]:
        return (f"{digest['iters']} iterations, the reference "
                f"{ref['iters']}")
    if digest["clusters"] != ref["clusters"]:
        return (f"{digest['clusters']} clusters, the reference "
                f"{ref['clusters']}")
    want = fingerprint(ref["labels"])
    if digest["fingerprint"] != want:
        return (f"label fingerprint {digest['fingerprint']}, the "
                f"reference {want}")
    got, exp = np.asarray(digest["chaos"], np.float64), np.asarray(
        ref["chaos"])
    off = np.abs(got - exp) - limits["chaos_rel"] * exp - limits["chaos_abs"]
    if np.any(off > 0):
        k = int(np.argmax(off))
        return (f"chaos of iteration {k + 1} is {got[k]:.8g}, the "
                f"reference {exp[k]:.8g}")
    got, exp = np.asarray(digest["stored"], np.float64), np.asarray(
        ref["stored"], np.float64)
    off = np.abs(got - exp) - limits["stored_rel"] * exp
    if np.any(off > 0):
        k = int(np.argmax(off))
        return (f"{int(got[k])} stored after iteration {k + 1}, the "
                f"reference {int(exp[k])}")
    return None


def check_matrix(n, got, want: sp.csc_matrix, columns, limits: dict,
                 which: str = "") -> tuple[str | None, float, float]:
    """One handed-out state against the reference's, cut to ``columns``:
    ``(problem, largest, mean)`` column distance, held to
    ``limits['column_l1_max']`` and ``['column_l1_mean']``."""
    d = column_distances(n, got, want, columns)
    worst, mean = float(d.max()), float(d.mean())
    bad = None
    if worst > limits["column_l1_max"]:
        bad = (f"{which}: column {int(columns[int(np.argmax(d))])} lies "
               f"{worst:.3g} (L1) from the reference's, the limit "
               f"{limits['column_l1_max']}")
    elif mean > limits["column_l1_mean"]:
        bad = (f"{which}: the columns lie {mean:.3g} (L1) from the "
               f"reference's in the mean, the limit "
               f"{limits['column_l1_mean']}")
    return bad, worst, mean
