"""Upstream's ``MultTime`` product's plain reference: ``C = A @ A`` over
plus-times, with the digest that closes a job and the entry-for-entry
comparison of a C read back whole.

numpy / scipy only, nothing from the package: what decides ``correct``
cannot move with the program.  A is read from a COO edge list as the
deployment holds it, one unit value a stored entry (a repeated entry
would add, as it does on the device; the deployment's list has none),
and squared in int64 by scipy's CSR product.  ``C[i, j]`` is the number
of common neighbours of ``i`` and ``j``: integers, so the limit of every
comparison here is EQUALITY (``sqcontrol.py`` shows what the next
precision down, a bfloat16 accumulator, does to it).

The digest is the program's (``combblas_tpu/parallel/spgemm.py:
spgemm_digest``), restated: stored entries in all, their sum, and a row
its stored entries, their sum, and the fingerprint ``sum_j C[i, j] *
h(j)`` with ``h(j) = (j + 1) * 0x9E3779B1``, the last two in wrapping
32-bit arithmetic.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

#: the odd multiplier of the column hash
MULTIPLIER = 0x9E3779B1
_M32 = np.uint64(0xFFFFFFFF)


def column_hash(ncols: int) -> np.ndarray:
    """``h(j)`` for every column, uint64 holding 32 bits."""
    j = np.arange(1, ncols + 1, dtype=np.uint64)
    return (j * np.uint64(MULTIPLIER)) & _M32


def _as_int32(bits: np.ndarray) -> np.ndarray:
    """The low 32 bits of a uint64 array as int32 (two's complement)."""
    return (bits & _M32).astype(np.uint32).view(np.int32)


def digest_of(C: sp.csr_matrix) -> dict:
    """The digest of a canonical CSR matrix of integers."""
    n = C.shape[0]
    data = C.data.astype(np.int64)
    wrapped = data.astype(np.uint64)  # two's complement of a negative
    h = column_hash(C.shape[1])[C.indices]

    def by_row(x):
        cs = np.concatenate([[np.uint64(0)], np.cumsum(x, dtype=np.uint64)])
        return cs[C.indptr[1:]] - cs[C.indptr[:-1]]

    return {
        "nnz": int(C.nnz),
        "sum": int(data.sum()),
        "counts": np.diff(C.indptr).astype(np.int32),
        "sums": _as_int32(by_row(wrapped)),
        "prints": _as_int32(by_row((wrapped * h) & _M32)),
        "n": n,
    }


def canonical(n: int, rows, cols, vals) -> tuple[sp.csr_matrix, int]:
    """Stored tuples as a canonical CSR matrix of int64 (rows sorted,
    columns sorted inside a row), and how many tuples repeated a
    coordinate (they are summed, and counted)."""
    vals = np.asarray(vals)
    whole = np.asarray(np.rint(vals), np.int64)
    if not np.array_equal(whole, vals):
        raise ValueError("a stored value is not an integer")
    m = sp.coo_matrix(
        (whole, (np.asarray(rows), np.asarray(cols))), shape=(n, n)
    ).tocsr()
    m.sort_indices()
    return m, len(whole) - m.nnz


class SQReference:
    """``C = A @ A`` of the deployment's graph, and its digest."""

    def __init__(self, n: int, rows, cols):
        self.n = int(n)
        a = sp.csr_matrix(
            (np.ones(len(rows), np.int64), (rows, cols)), shape=(n, n))
        a.sum_duplicates()
        self.nnz_a = int(a.nnz)
        # one multiply for every pair (A[i, k], A[k, j])
        self.products = int(
            (np.diff(a.tocsc().indptr).astype(np.int64)
             * np.diff(a.indptr)).sum())
        c = (a @ a).tocsr()
        c.sort_indices()
        self.C = c
        self.digest = digest_of(c)
        self.largest = int(c.data.max(initial=0))

    def check_digest(self, digest: dict) -> str | None:
        """None when a job's digest is this product's; otherwise what
        differs.  Equality on every part."""
        want, bad = self.digest, []
        for key in ("nnz", "sum"):
            got = digest.get(key)
            if not isinstance(got, (int, np.integer)):
                bad.append(f"{key} {got!r} is not an integer")
            elif int(got) != want[key]:
                bad.append(
                    f"{key} {int(got)}, the reference's is {want[key]} "
                    f"(off by {int(got) - want[key]})")
        for key in ("counts", "sums", "prints"):
            got = np.asarray(digest.get(key))
            if got.shape != (self.n,) or got.dtype != np.int32:
                bad.append(
                    f"{key} is {got.dtype}{list(got.shape)}, not "
                    f"int32[{self.n}]")
                continue
            off = np.flatnonzero(got != want[key])
            if len(off):
                i = int(off[0])
                bad.append(
                    f"{key} differs in {len(off)} rows, first row {i}: "
                    f"{int(got[i])}, the reference's {int(want[key][i])}")
        return "; ".join(bad) or None

    def check_entries(self, rows, cols, vals) -> str | None:
        """None when the stored tuples of a C read back whole are this
        product's, entry for entry: the same coordinates, each once, and
        the same value at each.  Otherwise what differs."""
        try:
            got, repeated = canonical(self.n, rows, cols, vals)
        except ValueError as e:
            return str(e)
        if repeated:
            return f"{repeated} stored tuples repeat a coordinate"
        want = self.C
        if got.nnz != want.nnz or not (
                np.array_equal(got.indptr, want.indptr)
                and np.array_equal(got.indices, want.indices)):
            diff = (got != 0) != (want != 0)
            r, c = diff.nonzero()
            where = f"({int(r[0])}, {int(c[0])})" if len(r) else "none"
            return (
                f"{got.nnz} entries, the reference has {want.nnz}; "
                f"{len(r)} coordinates are in one and not the other, "
                f"first {where}")
        off = np.flatnonzero(got.data != want.data)
        if len(off):
            k = int(off[0])
            i = int(np.searchsorted(want.indptr, k, side="right") - 1)
            return (
                f"{len(off)} of {want.nnz} entries hold another value, "
                f"first ({i}, {int(want.indices[k])}): "
                f"{int(got.data[k])}, the reference's {int(want.data[k])}")
        return None
