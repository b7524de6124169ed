"""SpTuples — padded static-capacity COO tile, the interchange format.

Mirrors the role of the reference's ``SpTuples<IT,NT>``
(``include/CombBLAS/SpTuples.h:64-120``): the column/row-sorted triple format
every kernel, merge, redistribution, and I/O path speaks.  The TPU-native
difference: XLA requires static shapes, so a tile carries a fixed ``capacity``
of slots plus a dynamic ``nnz`` scalar.  Invalid (padding) slots hold
``row == nrows, col == ncols`` so that

* scatters drop them (out-of-range + ``mode='drop'``),
* row-major / col-major sorts push them to the tail,
* gathers hit a dedicated padded slot holding the semiring zero.

All ops are jit-compatible; ``nrows/ncols/capacity`` are trace-time static.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..semiring import Semiring
from .segment import segment_reduce

Array = jax.Array


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["rows", "cols", "vals", "nnz"],
    meta_fields=["nrows", "ncols"],
)
@dataclasses.dataclass(frozen=True)
class SpTuples:
    """Padded COO tile. Valid entries occupy a prefix iff compacted.

    rows/cols: int32[cap]; padding slots hold (nrows, ncols).
    vals: NT[cap]; padding values are unspecified (protected by index drop).
    nnz: int32 scalar — number of valid entries.
    """

    rows: Array
    cols: Array
    vals: Array
    nnz: Array
    nrows: int
    ncols: int

    @property
    def capacity(self) -> int:
        return self.rows.shape[0]

    @property
    def dtype(self):
        return self.vals.dtype

    # --- constructors -----------------------------------------------------

    @staticmethod
    def from_coo(rows, cols, vals, nrows, ncols, capacity=None) -> "SpTuples":
        """Build from concrete (host) index/value arrays (unsorted ok)."""
        rows = np.asarray(rows, dtype=np.int32)
        cols = np.asarray(cols, dtype=np.int32)
        vals = np.asarray(vals)
        n = rows.shape[0]
        cap = int(capacity) if capacity is not None else max(n, 1)
        if n > cap:
            raise ValueError(f"nnz {n} exceeds capacity {cap}")
        pr = np.full(cap, nrows, dtype=np.int32)
        pc = np.full(cap, ncols, dtype=np.int32)
        pv = np.zeros(cap, dtype=vals.dtype)
        pr[:n], pc[:n], pv[:n] = rows, cols, vals
        return SpTuples(
            rows=jnp.asarray(pr),
            cols=jnp.asarray(pc),
            vals=jnp.asarray(pv),
            nnz=jnp.asarray(n, dtype=jnp.int32),
            nrows=int(nrows),
            ncols=int(ncols),
        )

    @staticmethod
    def from_dense(dense, capacity=None, zero=0) -> "SpTuples":
        """Host-side convenience (tests / small inputs)."""
        dense = np.asarray(dense)
        r, c = np.nonzero(dense != zero)
        return SpTuples.from_coo(
            r, c, dense[r, c], dense.shape[0], dense.shape[1], capacity
        )

    @staticmethod
    def empty(nrows, ncols, capacity, dtype) -> "SpTuples":
        return SpTuples(
            rows=jnp.full((capacity,), nrows, dtype=jnp.int32),
            cols=jnp.full((capacity,), ncols, dtype=jnp.int32),
            vals=jnp.zeros((capacity,), dtype=dtype),
            nnz=jnp.asarray(0, dtype=jnp.int32),
            nrows=int(nrows),
            ncols=int(ncols),
        )

    # --- basic queries ----------------------------------------------------

    def valid_mask(self) -> Array:
        return self.rows < self.nrows

    def to_dense(self, sr: Semiring = None) -> Array:
        """Densify; duplicates are combined with ``sr.add`` (default: sum)."""
        zero = sr.zero(self.dtype) if sr is not None else jnp.zeros((), self.dtype)
        out = jnp.full((self.nrows + 1, self.ncols + 1), zero, dtype=self.dtype)
        if sr is None or sr.add_kind == "sum":
            out = out.at[self.rows, self.cols].add(
                jnp.where(self.valid_mask(), self.vals, 0), mode="drop"
            )
        elif sr.add_kind == "min":
            out = out.at[self.rows, self.cols].min(self.vals, mode="drop")
        elif sr.add_kind == "max":
            out = out.at[self.rows, self.cols].max(self.vals, mode="drop")
        else:
            # Generic monoid: flatten (row, col) to one segment id and run the
            # order-respecting segmented reduction (scatter .set would be
            # last-write-wins with unspecified order).
            flat_ids = self.rows * (self.ncols + 1) + self.cols
            flat = segment_reduce(
                sr, self.vals, flat_ids, (self.nrows + 1) * (self.ncols + 1)
            )
            out = flat.reshape(self.nrows + 1, self.ncols + 1)
        return out[: self.nrows, : self.ncols]

    # --- structural transforms -------------------------------------------

    def sort_rowmajor(self) -> "SpTuples":
        # A fused single-uint32-key variant was tried and measured on the
        # target chip: no improvement over the two-key sort
        # (round-2 microbench, 28.6s vs 26.6s) — the
        # sort is bandwidth/pass-bound, not operand-count-bound.
        r, c, v = lax.sort((self.rows, self.cols, self.vals), num_keys=2)
        return dataclasses.replace(self, rows=r, cols=c, vals=v)

    def sort_colmajor(self) -> "SpTuples":
        c, r, v = lax.sort((self.cols, self.rows, self.vals), num_keys=2)
        return dataclasses.replace(self, rows=r, cols=c, vals=v)

    def transpose(self) -> "SpTuples":
        """Swap rows/cols. Reference: ``SpTuples`` transpose ctor flag."""
        return SpTuples(
            rows=jnp.where(self.valid_mask(), self.cols, self.ncols),
            cols=jnp.where(self.valid_mask(), self.rows, self.nrows),
            vals=self.vals,
            nnz=self.nnz,
            nrows=self.ncols,
            ncols=self.nrows,
        )

    def with_capacity(self, capacity: int) -> "SpTuples":
        """Grow/shrink the slot count.

        Shrinking requires a compacted tile with ``nnz <= capacity``; entries
        beyond the new capacity are lost and ``nnz`` is clamped to match.
        """
        cap = self.capacity
        if capacity == cap:
            return self
        if capacity > cap:
            pad = capacity - cap
            return dataclasses.replace(
                self,
                rows=jnp.concatenate(
                    [self.rows, jnp.full((pad,), self.nrows, jnp.int32)]
                ),
                cols=jnp.concatenate(
                    [self.cols, jnp.full((pad,), self.ncols, jnp.int32)]
                ),
                vals=jnp.concatenate(
                    [self.vals, jnp.zeros((pad,), self.vals.dtype)]
                ),
            )
        return dataclasses.replace(
            self,
            rows=self.rows[:capacity],
            cols=self.cols[:capacity],
            vals=self.vals[:capacity],
            nnz=jnp.minimum(self.nnz, jnp.int32(capacity)),
        )

    def compact_counted(
        self,
        sr: Semiring,
        *,
        capacity: int | None = None,
        assume_sorted: bool = False,
    ) -> tuple["SpTuples", Array]:
        """``compact`` that also returns the EXACT distinct-key count
        (before any truncation) — the per-tile role of the reference's
        ``estimateNNZ_Hash`` (mtSpGEMM.h:807): callers compare it against
        ``capacity`` to detect truncation and retry with exact sizing.

        Sort row-major, combine duplicates with ``sr.add``, drop explicit
        zeros, and pack valid entries to the front.

        Mirrors ``SpTuples::RemoveDuplicates(BinOp)`` (SpTuples.h:89) plus the
        sort that every DCSC build performs.

        INVARIANT: ``capacity`` must be >= the number of distinct (row, col)
        keys; entries whose combined slot lands beyond it are truncated (the
        static-shape price of XLA — callers size capacities from symbolic
        estimates, see ops/spgemm.py). ``nnz`` is clamped to ``capacity`` so
        the result stays self-consistent either way.

        ``assume_sorted=True`` skips the row-major sort (caller guarantees
        slots are already (row, col)-sorted with padding at the tail).
        """
        cap = capacity if capacity is not None else self.capacity
        t = self if assume_sorted else self.sort_rowmajor()
        valid = t.valid_mask()
        prev_same = jnp.concatenate(
            [
                jnp.zeros((1,), bool),
                (t.rows[1:] == t.rows[:-1]) & (t.cols[1:] == t.cols[:-1]),
            ]
        )
        is_new = valid & ~prev_same
        seg = jnp.cumsum(is_new.astype(jnp.int32)) - 1
        seg = jnp.where(valid, seg, cap)
        vals = segment_reduce(sr, t.vals, seg, cap, ids_sorted=True)
        distinct = jnp.sum(is_new).astype(jnp.int32)
        # ONE input-sized permutation scatter + output-sized gathers
        # (instead of one input-sized scatter per index array): the output
        # is typically several-fold smaller than the expansion, and this
        # chip prices scatters/gathers per ELEMENT (~22-27 M/s,
        # round-3 scatter probe).
        # distinct OOB sentinels keep the unique_indices contract for the
        # dropped (non-representative) slots
        slot_ids = jnp.arange(t.capacity, dtype=jnp.int32)
        scatter_idx = jnp.where(is_new, seg, cap + slot_ids)
        perm = jnp.zeros((cap,), jnp.int32).at[scatter_idx].set(
            slot_ids, mode="drop", unique_indices=True,
        )
        out_valid = jnp.arange(cap, dtype=jnp.int32) < distinct
        rows = jnp.where(out_valid, t.rows[perm], self.nrows)
        cols = jnp.where(out_valid, t.cols[perm], self.ncols)
        nnz = jnp.minimum(distinct, jnp.int32(cap))
        out = SpTuples(
            rows=rows, cols=cols, vals=vals, nnz=nnz,
            nrows=self.nrows, ncols=self.ncols,
        )
        return out.prune_zeros(sr), distinct

    def compact(
        self,
        sr: Semiring,
        *,
        capacity: int | None = None,
        assume_sorted: bool = False,
    ) -> "SpTuples":
        out, _ = self.compact_counted(
            sr, capacity=capacity, assume_sorted=assume_sorted
        )
        return out

    def prune_zeros(self, sr: Semiring) -> "SpTuples":
        """Drop entries equal to the additive identity (compacted output)."""
        zero = sr.zero(self.dtype)
        keep = self.valid_mask() & (self.vals != zero)
        return self._select(keep)

    def prune(self, pred) -> "SpTuples":
        """Drop entries where ``pred(val)`` is True.

        Reference: ``SpParMat::Prune`` (SpParMat.h:162-198) local part.
        """
        keep = self.valid_mask() & ~pred(self.vals)
        return self._select(keep)

    def select_ij(self, keep_fn) -> "SpTuples":
        """Keep entries where ``keep_fn(row, col)`` (tile-local ids) is True.

        The structural counterpart of ``prune``: used for tril/triu/
        RemoveLoops (reference ``SpParMat::PruneI`` / ``RemoveLoops``,
        SpParMat.cpp:3257).
        """
        keep = self.valid_mask() & keep_fn(self.rows, self.cols)
        return self._select(keep)

    def _select(self, keep: Array) -> "SpTuples":
        """Stable-compact entries where ``keep`` to the front.

        One permutation scatter + per-array gathers (not one scatter per
        array): scatters and gathers cost the same per element on the
        target chip, so 1 scatter + 3 gathers beats 3 scatters whenever
        XLA can fuse the gathers, and never loses.
        """
        cap = self.capacity
        nkeep = jnp.sum(keep).astype(jnp.int32)
        pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
        slot_ids = jnp.arange(cap, dtype=jnp.int32)
        scatter_idx = jnp.where(keep, pos, cap + slot_ids)
        perm = jnp.zeros((cap,), jnp.int32).at[scatter_idx].set(
            slot_ids, mode="drop", unique_indices=True,
        )
        out_valid = slot_ids < nkeep
        return SpTuples(
            rows=jnp.where(out_valid, self.rows[perm], self.nrows),
            cols=jnp.where(out_valid, self.cols[perm], self.ncols),
            vals=jnp.where(out_valid, self.vals[perm], 0),
            nnz=nkeep,
            nrows=self.nrows, ncols=self.ncols,
        )

    def apply(self, fn) -> "SpTuples":
        """Elementwise value transform on valid entries.

        Reference: ``SpParMat::Apply`` (SpParMat.h:148).
        """
        vals = jnp.where(self.valid_mask(), fn(self.vals), self.vals)
        return dataclasses.replace(self, vals=vals)

    # --- concatenation (merge input) -------------------------------------

    @staticmethod
    def concat(tiles: list["SpTuples"]) -> "SpTuples":
        """Stack slot arrays of same-shape tiles (pre-merge). All tiles must
        share (nrows, ncols). Output capacity = sum of capacities."""
        t0 = tiles[0]
        return SpTuples(
            rows=jnp.concatenate([t.rows for t in tiles]),
            cols=jnp.concatenate([t.cols for t in tiles]),
            vals=jnp.concatenate([t.vals for t in tiles]),
            nnz=sum((t.nnz for t in tiles[1:]), start=t0.nnz),
            nrows=t0.nrows,
            ncols=t0.ncols,
        )
