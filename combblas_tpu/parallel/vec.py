"""Distributed dense / sparse vectors (≈ FullyDistVec / FullyDistSpVec).

The reference distributes vectors over ALL p processes in matrix-conformant
two-level blocks (``include/CombBLAS/FullyDist.h:44-57``) so that the
column-world allgather re-assembles exactly the x-block a local tile needs.
On TPU the replication that MPI must construct by communication comes for
free from sharding: a vector is stored as ``[pa, L]`` blocks sharded over ONE
mesh axis and *replicated* over the other by XLA — so the reference's
``TransposeVector + AllGatherVector`` pre-phase (``ParFriends.h:1388-1478``)
vanishes from SpMV entirely; only alignment conversions pay communication.

Alignment:
  * ``"col"``-aligned: block j lives on grid column j (what SpMV consumes).
  * ``"row"``-aligned: block i lives on grid row i (what SpMV produces).

``realign`` converts between them — a ``ppermute`` complement-rank pair
exchange on square grids (the reference's diagonal Sendrecv,
``SpParMat.cpp:3554-3570``), falling back to allgather+slice on rectangular
grids.

Sparse vectors (``SpDistVec``) carry padded (ind, val) slot arrays + nnz,
mirroring ``FullyDistSpVec``'s ind/num arrays (``FullyDistSpVec.h:75``).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.segment import segment_reduce
from ..semiring import Semiring
from .collectives import axis_reduce
from .grid import COL_AXIS, ROW_AXIS, Grid

Array = jax.Array


def _np_pad_blocks(x: np.ndarray, nblocks: int, fill) -> np.ndarray:
    L = -(-x.shape[0] // nblocks)
    out = np.full((nblocks, L), fill, dtype=x.dtype)
    flat = out.reshape(-1)
    flat[: x.shape[0]] = x
    return flat.reshape(nblocks, L)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["blocks"],
    meta_fields=["length", "align", "grid"],
)
@dataclasses.dataclass(frozen=True)
class DistVec:
    """Dense distributed vector: ``blocks[pa, L]`` sharded over one mesh axis.

    Padding slots (beyond ``length``) must hold values that are inert for the
    ops applied to them (constructors fill the reduction identity).
    """

    blocks: Array  # [pa, L]
    length: int
    align: str  # "row" | "col"
    grid: Grid

    @property
    def nblocks(self) -> int:
        return self.blocks.shape[0]

    @property
    def block_len(self) -> int:
        return self.blocks.shape[1]

    def axis_name(self) -> str:
        # Blocks of a row-aligned vector vary over grid rows (mesh axis "r").
        return ROW_AXIS if self.align == "row" else COL_AXIS

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.grid.mesh, P(self.axis_name()))

    # --- construction -----------------------------------------------------

    @staticmethod
    def from_global(grid: Grid, x, align: str = "col", fill=0) -> "DistVec":
        x = np.asarray(x)
        pa = grid.pr if align == "row" else grid.pc
        blocks = _np_pad_blocks(x, pa, np.asarray(fill, dtype=x.dtype))
        sharding = NamedSharding(
            grid.mesh, P(ROW_AXIS if align == "row" else COL_AXIS)
        )
        return DistVec(
            blocks=jax.device_put(jnp.asarray(blocks), sharding),
            length=int(x.shape[0]),
            align=align,
            grid=grid,
        )

    @staticmethod
    def full(grid: Grid, length: int, value, dtype, align: str = "col") -> "DistVec":
        pa = grid.pr if align == "row" else grid.pc
        L = -(-length // pa)
        sharding = NamedSharding(
            grid.mesh, P(ROW_AXIS if align == "row" else COL_AXIS)
        )
        blocks = jax.device_put(
            jnp.full((pa, L), value, dtype=dtype), sharding
        )
        return DistVec(blocks=blocks, length=length, align=align, grid=grid)

    @staticmethod
    def iota(grid: Grid, length: int, dtype=jnp.int32, align: str = "col") -> "DistVec":
        """Reference: ``FullyDistVec::iota``."""
        pa = grid.pr if align == "row" else grid.pc
        L = -(-length // pa)
        vals = jnp.arange(pa * L, dtype=dtype).reshape(pa, L)
        sharding = NamedSharding(
            grid.mesh, P(ROW_AXIS if align == "row" else COL_AXIS)
        )
        return DistVec(
            blocks=jax.device_put(vals, sharding),
            length=length, align=align, grid=grid,
        )

    # --- host access (tests / small data) ---------------------------------

    def to_global(self) -> np.ndarray:
        return np.asarray(self.blocks).reshape(-1)[: self.length]

    # --- elementwise ------------------------------------------------------

    def apply(self, fn) -> "DistVec":
        """Reference: ``FullyDistVec::Apply``."""
        return dataclasses.replace(self, blocks=fn(self.blocks))

    def ewise(self, other: "DistVec", fn) -> "DistVec":
        """Blockwise binary op; alignments must match.

        Reference: ``FullyDistVec::EWiseApply`` (FullyDistVec.h).
        """
        assert self.align == other.align and self.length == other.length
        return dataclasses.replace(self, blocks=fn(self.blocks, other.blocks))

    def mask_padding(self, fill) -> "DistVec":
        """Force padding slots (global index >= length) to ``fill``."""
        pa, L = self.blocks.shape
        gids = jnp.arange(pa * L).reshape(pa, L)
        return dataclasses.replace(
            self,
            blocks=jnp.where(gids < self.length, self.blocks, fill),
        )

    # --- indirect addressing (the FullyDistVec subsref/ReduceAssign pair) --

    def gather(self, idx: "DistVec") -> "DistVec":
        """out[k] = self[idx[k]] — distributed vector subscript.

        Reference: ``FullyDistVec::operator()(FullyDistVec ri)`` (subsref,
        FullyDistVec.cpp) — there an Alltoallv request/response exchange; here
        a plain sharded gather, with GSPMD inserting the all-gather of
        ``self`` over ICI.  idx values must lie in [0, self.length); anything
        else (including idx's own padding slots) reads an unspecified slot —
        callers must mask those results.  Result is aligned like ``idx``.
        """
        full = self.blocks.reshape(-1)
        safe = jnp.clip(idx.blocks, 0, full.shape[0] - 1)
        return DistVec(
            blocks=full[safe],
            length=idx.length,
            align=idx.align,
            grid=idx.grid,
        )

    def scatter_combine(
        self, sr: Semiring, idx: "DistVec", src: "DistVec"
    ) -> "DistVec":
        """out[p] = sr.add(self[p], ⊕{src[k] : idx[k] == p}).

        Reference: ``FullyDistVec::ReduceAssign`` / the scatter helper used
        by LACC & FastSV hooking (CC.h:1033-1230, FastSV.h:68-146) — there an
        Alltoallv of (index, value) pairs + local fold; here one segment
        reduction over the flattened blocks (identity-filled empty segments
        make the final elementwise ``add`` a no-op for untouched slots).
        idx/src must share alignment and shape with each other; padding slots
        of idx (beyond idx.length) are dropped.
        """
        assert idx.align == src.align and idx.length == src.length
        pa, L = self.blocks.shape
        ids = idx.blocks.reshape(-1)
        vals = src.blocks.reshape(-1)
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        ids = jnp.where(pos < idx.length, ids, pa * L)  # drop padding sources
        ids = jnp.where((ids >= 0) & (ids < self.length), ids, pa * L)
        contrib = segment_reduce(sr, vals, ids, pa * L)
        out = sr.add(self.blocks.reshape(-1), contrib)
        return dataclasses.replace(self, blocks=out.reshape(pa, L))

    def reduce(self, sr: Semiring) -> Array:
        """Global fold with sr.add → replicated scalar.

        Padding must hold the identity (use mask_padding first if unsure).
        Reference: ``FullyDistVec::Reduce``.
        """
        if sr.add_kind == "sum":
            return jnp.sum(self.blocks)
        if sr.add_kind == "min":
            return jnp.min(self.blocks)
        if sr.add_kind == "max":
            return jnp.max(self.blocks)
        return jax.lax.reduce(
            self.blocks, sr.zero(self.blocks.dtype), sr.add, (0, 1)
        )

    # --- FullyDistVec op pack (sort / find / permute family) ---------------

    def sort(self) -> tuple["DistVec", "DistVec"]:
        """Ascending sort. Returns (sorted values, original indices).

        Reference: ``FullyDistVec::sort`` (there a psort; here XLA's native
        sharded sort over the global view — the distributed-sorting strategy
        of SURVEY §2.3(8) collapses into one collective sort on ICI).
        Padding slots sort to the tail regardless of their value.
        """
        return _sort_jit(self)

    def find_inds(self, pred) -> tuple["DistVec", Array]:
        """Global indices i (ascending) with ``pred(self[i])``.

        Reference: ``FullyDistVec::FindInds`` — there a variable-length
        result vector; here a fixed-capacity DistVec whose first ``count``
        slots hold the indices and whose tail holds the sentinel
        ``self.length``. Returns (indices, count). Pass a module-level
        ``pred`` for compile-cache hits.
        """
        return _find_inds_jit(self, pred)

    def invert(self, active: "DistVec", out_length: int, sr: Semiring) -> "DistVec":
        """out[self[i]] = i for active slots i; collisions resolved by
        ``sr.add``; untouched outputs get -1.

        Reference: ``FullyDistSpVec::Invert`` (FullyDistSpVec.h:89-93) — the
        value↔index flip with duplicate resolution. ``active`` is the
        bool mask standing in for the sparse vector's index set (our
        masked-dense FullyDistSpVec representation).
        """
        return _invert_jit(self, active, out_length, sr)

    def uniq(self, active: "DistVec") -> "DistVec":
        """New active mask keeping only the first (lowest-index) occurrence
        of each value among active slots.

        Reference: ``FullyDistSpVec::Uniq``. Setminus, the other index-set
        op of that family, is plain mask arithmetic on masked-dense vectors:
        ``a_active & ~b_active``.
        """
        return _uniq_jit(self, active)

    @staticmethod
    def randperm(grid: Grid, length: int, key, align: str = "col") -> "DistVec":
        """Uniform random permutation of [0, length).

        Reference: ``FullyDistVec::RandPerm`` (FullyDistVec.cpp:783-870) —
        there a random-destination Alltoallv + local shuffle; here one
        sort-by-random-key over the sharded global view.  ``key`` is a JAX
        PRNG key (the deterministic-stream analog of the reference's
        per-rank seeds).
        """
        v = DistVec.iota(grid, length, jnp.int32, align=align)
        return _randperm_jit(v, key)

    # --- alignment conversion (the TransposeVector analog) ----------------

    def realign(self, align: str) -> "DistVec":
        if align == self.align:
            return self
        with jax.named_scope("vec.realign"):
            return self._realign(align)

    def _realign(self, align: str) -> "DistVec":
        grid = self.grid
        src_axis = self.axis_name()
        dst_pa = grid.pr if align == "row" else grid.pc
        dst_sharding = NamedSharding(
            grid.mesh, P(ROW_AXIS if align == "row" else COL_AXIS)
        )

        if grid.is_square:
            # Complement-rank pair exchange: device (i,j) holds block i
            # (row-aligned); after ppermute from (j,i), it holds block j.
            perm = grid.transpose_perm()

            def shift(b):  # b: [1, L]
                return lax.ppermute(b, (ROW_AXIS, COL_AXIS), perm)

            blocks = jax.shard_map(
                shift,
                mesh=grid.mesh,
                in_specs=P(src_axis),
                out_specs=P(ROW_AXIS if align == "row" else COL_AXIS),
                # The permutation provably delivers block j to every (i, j),
                # i.e. the output IS replicated along the unlisted axis, but
                # shard_map cannot infer that through ppermute.
                check_vma=False,
            )(self.blocks)
        else:
            # Rectangular grid: allgather the full vector along the source
            # axis, then let resharding slice out the destination blocks.
            full = self.blocks.reshape(-1)
            pa = dst_pa
            L = -(-full.shape[0] // pa)
            pad = pa * L - full.shape[0]
            if pad:
                full = jnp.concatenate([full, jnp.zeros((pad,), full.dtype)])
            blocks = jax.device_put(full.reshape(pa, L), dst_sharding)
        return DistVec(
            blocks=blocks, length=self.length, align=align, grid=grid
        )


# --- jitted impls of the op pack -------------------------------------------


def _global_ids(vec: DistVec) -> Array:
    pa, L = vec.blocks.shape
    return jnp.arange(pa * L, dtype=jnp.int32)


@jax.jit
def _sort_jit(vec: DistVec) -> tuple[DistVec, DistVec]:
    flat = vec.blocks.reshape(-1)
    gids = _global_ids(vec)
    pad = (gids >= vec.length).astype(jnp.int32)
    _, vals, idx = lax.sort((pad, flat, gids), num_keys=2)
    shape = vec.blocks.shape
    return (
        dataclasses.replace(vec, blocks=vals.reshape(shape)),
        dataclasses.replace(vec, blocks=idx.reshape(shape)),
    )


@partial(jax.jit, static_argnames=("pred",))
def _find_inds_jit(vec: DistVec, pred) -> tuple[DistVec, Array]:
    pa, L = vec.blocks.shape
    flat = vec.blocks.reshape(-1)
    gids = _global_ids(vec)
    mask = pred(flat) & (gids < vec.length)
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    out = jnp.full((pa * L,), vec.length, jnp.int32)
    out = out.at[jnp.where(mask, pos, pa * L)].set(gids, mode="drop")
    count = jnp.sum(mask).astype(jnp.int32)
    return (
        DistVec(
            blocks=out.reshape(pa, L), length=vec.length, align=vec.align,
            grid=vec.grid,
        ),
        count,
    )


@partial(jax.jit, static_argnames=("out_length", "sr"))
def _invert_jit(
    vec: DistVec, active: DistVec, out_length: int, sr: Semiring
) -> DistVec:
    pa = vec.grid.pr if vec.align == "row" else vec.grid.pc
    L = -(-out_length // pa)
    flat = vec.blocks.reshape(-1).astype(jnp.int32)
    gids = _global_ids(vec)
    ok = active.blocks.reshape(-1) & (gids < vec.length)
    ids = jnp.where(ok & (flat >= 0) & (flat < out_length), flat, pa * L)
    contrib = segment_reduce(sr, gids, ids, pa * L)
    touched = jax.ops.segment_sum(
        ok.astype(jnp.int32), ids, num_segments=pa * L
    )
    out = jnp.where(touched > 0, contrib, -1)
    return DistVec(
        blocks=out.reshape(pa, L), length=out_length, align=vec.align,
        grid=vec.grid,
    )


@jax.jit
def _uniq_jit(vec: DistVec, active: DistVec) -> DistVec:
    pa, L = vec.blocks.shape
    flat = vec.blocks.reshape(-1)
    gids = _global_ids(vec)
    ok = active.blocks.reshape(-1) & (gids < vec.length)
    # Sort (inactive-last, value, gid); firsts of each active value run win.
    inact = (~ok).astype(jnp.int32)
    _, vals, idx = lax.sort((inact, flat, gids), num_keys=3)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), vals[1:] != vals[:-1]]
    )
    n_active = jnp.sum(ok)
    keep_sorted = first & (jnp.arange(pa * L) < n_active)
    keep = jnp.zeros((pa * L,), bool).at[idx].set(keep_sorted)
    return dataclasses.replace(active, blocks=keep.reshape(pa, L))


@jax.jit
def _randperm_jit(vec: DistVec, key) -> DistVec:
    pa, L = vec.blocks.shape
    gids = _global_ids(vec)
    # 64 bits of random key per element: float32 uniforms would alias to
    # 2^23 values and stable-sort ties toward identity order, biasing large
    # permutations. Padding sorts last via the explicit leading key.
    k1, k2 = jax.random.split(key)
    r1 = jax.random.bits(k1, (pa * L,), jnp.uint32)
    r2 = jax.random.bits(k2, (pa * L,), jnp.uint32)
    pad = (gids >= vec.length).astype(jnp.int32)
    _, _, _, perm = lax.sort(
        (pad, r1, r2, vec.blocks.reshape(-1)), num_keys=3
    )
    return dataclasses.replace(vec, blocks=perm.reshape(pa, L))


# --- multi-vector (batched frontier; ≈ BetwCent's frontier-as-matrix) -------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["blocks"],
    meta_fields=["length", "align", "grid"],
)
@dataclasses.dataclass(frozen=True)
class DistMultiVec:
    """W stacked distributed vectors: ``blocks[pa, L, W]``.

    The batched-frontier carrier for multi-source algorithms (Graph500's 64
    search keys, batched Brandes BC — SURVEY §2.3 strategy 7): one gathered
    index fetches W payload lanes, amortizing the per-index cost of TPU
    gathers across the batch (measured: W=8 costs the same as W=1 on v5e).
    Same alignment/padding contract as DistVec, width replicated everywhere.
    """

    blocks: Array  # [pa, L, W]
    length: int
    align: str  # "row" | "col"
    grid: Grid

    @property
    def width(self) -> int:
        return self.blocks.shape[2]

    @property
    def block_len(self) -> int:
        return self.blocks.shape[1]

    def axis_name(self) -> str:
        return ROW_AXIS if self.align == "row" else COL_AXIS

    @staticmethod
    def from_global(grid: Grid, x, align: str = "col", fill=0) -> "DistMultiVec":
        """x: [length, W] host array."""
        x = np.asarray(x)
        n, W = x.shape
        pa = grid.pr if align == "row" else grid.pc
        L = -(-n // pa)
        out = np.full((pa * L, W), fill, dtype=x.dtype)
        out[:n] = x
        sharding = NamedSharding(
            grid.mesh, P(ROW_AXIS if align == "row" else COL_AXIS)
        )
        return DistMultiVec(
            blocks=jax.device_put(jnp.asarray(out.reshape(pa, L, W)), sharding),
            length=int(n), align=align, grid=grid,
        )

    def to_global(self) -> np.ndarray:
        b = np.asarray(self.blocks)
        return b.reshape(-1, b.shape[2])[: self.length]

    def realign(self, align: str) -> "DistMultiVec":
        """Same exchange as DistVec.realign; the trailing width dim rides
        along (ppermute/all_gather are shape-agnostic past the block dim)."""
        if align == self.align:
            return self
        with jax.named_scope("vec.realign"):
            return self._realign(align)

    def _realign(self, align: str) -> "DistMultiVec":
        grid = self.grid
        src_axis = self.axis_name()
        dst_axis = ROW_AXIS if align == "row" else COL_AXIS
        dst_pa = grid.pr if align == "row" else grid.pc
        dst_sharding = NamedSharding(grid.mesh, P(dst_axis))
        if grid.is_square:
            perm = grid.transpose_perm()

            def shift(b):  # [1, L, W]
                return lax.ppermute(b, (ROW_AXIS, COL_AXIS), perm)

            blocks = jax.shard_map(
                shift,
                mesh=grid.mesh,
                in_specs=P(src_axis),
                out_specs=P(dst_axis),
                check_vma=False,
            )(self.blocks)
        else:
            W = self.width
            full = self.blocks.reshape(-1, W)
            L = -(-full.shape[0] // dst_pa)
            pad = dst_pa * L - full.shape[0]
            if pad:
                full = jnp.concatenate(
                    [full, jnp.zeros((pad, W), full.dtype)]
                )
            blocks = jax.device_put(
                full.reshape(dst_pa, L, W), dst_sharding
            )
        return DistMultiVec(
            blocks=blocks, length=self.length, align=align, grid=grid
        )


def concatenate(vecs, grid: "Grid | None" = None, align: str | None = None,
                fill=0) -> DistVec:
    """Cross-grid vector concatenation (≈ ``Concatenate``,
    ParFriends.h:61-159).

    The reference stitches FullyDistVecs living on DIFFERENT process grids
    into one vector on the union grid via pairwise exchanges. Here vectors
    may live on different meshes (or the same one): each input's blocks
    are flattened device-side, concatenated in order, re-padded, and
    device_put onto the target grid's sharding — XLA moves the bytes
    between device sets at the jit boundary. ``grid`` defaults to the
    first vector's grid; ``align`` to the first vector's alignment.
    """
    assert vecs, "concatenate needs at least one vector"
    grid = grid or vecs[0].grid
    align = align or vecs[0].align
    pa = grid.pr if align == "row" else grid.pc
    total = sum(v.length for v in vecs)
    # inputs may live on different device sets: land every part on the
    # TARGET mesh (replicated) before concatenating — the cross-grid hop
    rep = NamedSharding(grid.mesh, P())
    parts = [
        jax.device_put(v.blocks.reshape(-1)[: v.length], rep) for v in vecs
    ]
    flat = jnp.concatenate(parts)
    L = -(-total // pa)
    pad = pa * L - total
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), fill, flat.dtype)]
        )
    sharding = NamedSharding(
        grid.mesh, P(ROW_AXIS if align == "row" else COL_AXIS)
    )
    return DistVec(
        blocks=jax.device_put(flat.reshape(pa, L), sharding),
        length=total, align=align, grid=grid,
    )
