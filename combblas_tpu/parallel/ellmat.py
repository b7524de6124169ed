"""EllParMat — bucketed sliced-ELL, the gather-only distributed SpMV format.

The reference's answer to SpMV efficiency is DCSC column walks + per-thread
row splits (``Friends.h:64-180``). On TPU the bottleneck inverts: gathers
are essentially free (HBM-bandwidth vectorized) while large scatters and
segmented scans serialize — a 16M-entry segment-max takes seconds where the
equivalent dense-gather formulation takes 0.05 ms (measured, v5e).

Scale-free graphs defeat plain ELL (one k covers the median but hubs push
most nnz into an overflow scatter — 61% of scale-19 R-MAT at k=64). The
fix is degree-bucketed sliced ELL: rows are grouped by degree class on a
1.5-step width ladder (1,2,3,4,6,8,12,...; ``_width_ladder``); bucket b
stores its rows densely as ``[nb, kb]`` with kb = ladder[b], so

* every row's entries live in exactly one bucket (no overflow COO),
* each bucket's fold is a DENSE reduction over its k axis (VPU-native),
* results scatter back by unique row ids — an n-sized .set scatter, cheap,
* total storage is < 1.5x nnz (kb < 1.5 x degree; measured 1.15x on
  scale-20 R-MAT, worth +12% end-to-end BFS on the target chip).

This is the reference's DER-swap seam (``SpMat.h:54``): same distributed
schedule (x replicated down grid columns, fold over the "c" axis), local
kernel chosen by type — ``dist_spmv``/``dist_spmv_masked`` dispatch on the
matrix type, so SpMV-only algorithms (BFS, CC, SSSP, MIS) accept an
EllParMat unchanged. Algorithms needing column reductions, apply, or the
SpMSpV path (PageRank's normalization, bfs_diropt) keep SpParMat.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.segment import segment_reduce
from ..semiring import Semiring
from .collectives import axis_reduce
from .grid import COL_AXIS, ROW_AXIS, Grid
from .spmat import SpParMat, TILE_SPEC
from .vec import DistVec

Array = jax.Array


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["buckets"],
    meta_fields=["nrows", "ncols", "grid"],
)
@dataclasses.dataclass(frozen=True)
class EllParMat:
    """buckets: tuple of (cols [pr,pc,nb,kb], vals [pr,pc,nb,kb],
    rowids [pr,pc,nb]) — one entry per populated degree class.

    Padding: col slots hold local_cols (gathers the semiring zero), padded
    bucket rows hold rowid = local_rows (dropped by the result scatter).
    """

    buckets: tuple
    nrows: int
    ncols: int
    grid: Grid

    @property
    def local_rows(self) -> int:
        return self.grid.local_rows(self.nrows)

    @property
    def local_cols(self) -> int:
        return self.grid.local_cols(self.ncols)

    @property
    def dtype(self):
        return self.buckets[0][1].dtype if self.buckets else jnp.float32

    def getnnz(self) -> Array:
        lc = self.local_cols
        return sum(
            (jnp.sum(bc < lc) for bc, _, _ in self.buckets),
            start=jnp.int32(0),
        )

    @staticmethod
    def from_host_coo(
        grid: Grid, rows, cols, vals, nrows: int, ncols: int,
        max_k: int | None = None, ladder: str = "fine",
        headroom: float | None = None,
    ) -> "EllParMat":
        """Build directly from host global COO — fully numpy + one upload,
        no device readback.

        ``max_k`` caps a bucket's width; rows with degree > max_k span
        multiple bucket rows whose partial folds recombine in the result
        scatter via the semiring add (each entry still appears once).

        ``ladder``: ``"fine"`` (default) uses the 1.5-step width ladder —
        ~1.15x slot padding, +12% on W=256 batched BFS; ``"coarse"`` uses
        power-of-two widths — FEWER bucket classes (fewer small gathers
        per sweep), measurably better for 1-lane payloads (single-vector
        SpMV) which cannot amortize the extra per-bucket sweeps.

        ``headroom`` (default: env ``COMBBLAS_DYNAMIC_HEADROOM``, 0)
        over-allocates every bucket class by that fraction of FREE
        padding rows: a high-churn dynamic graph's growing rows then
        re-bucket into the reserved slots (``dynamic.merge.
        headroom_used``) instead of spilling the whole merge to a
        rebuild (``dynamic.merge.spill{reason=bucket_full}``).
        """
        host = EllParMat.host_build(
            grid, rows, cols, vals, nrows, ncols, max_k=max_k,
            ladder=ladder, headroom=headroom,
        )
        return EllParMat.from_host_buckets(grid, host, nrows, ncols)

    @staticmethod
    def from_host_buckets(
        grid: Grid, host_buckets, nrows: int, ncols: int
    ) -> "EllParMat":
        """Upload pre-built host bucket arrays (``host_build`` output, or
        the same arrays round-tripped through an .npz): one device_put per
        array — the bench protocol's cheap per-child path (the parent
        builds once on host; children only upload)."""
        sh = grid.tile_sharding()
        # host array -> its shards directly: going through jnp.asarray
        # first would stage the WHOLE array on device 0
        put = lambda x: jax.device_put(np.asarray(x), sh)
        return EllParMat(
            buckets=tuple(
                (put(bc), put(bv), put(br)) for bc, bv, br in host_buckets
            ),
            nrows=int(nrows), ncols=int(ncols), grid=grid,
        )

    @staticmethod
    def host_build(
        grid: Grid, rows, cols, vals, nrows: int, ncols: int,
        max_k: int | None = None, ladder: str = "fine",
        headroom: float | None = None,
    ):
        """HOST-ONLY bucket construction (no device touch): returns a list
        of (bc, bv, br) numpy arrays — the serializable half of
        ``from_host_coo``, split out so a bench parent process can build
        once and ship the arrays to timing children via .npz without ever
        attaching to the chip itself.  ``headroom`` reserves extra free
        padding rows per class (see ``from_host_coo``)."""
        from ..tuner import config as tuner_config
        from .spmat import bucket_by_tile

        headroom = tuner_config.dynamic_headroom(headroom)

        vals = np.asarray(vals)
        rows, cols, order, counts, starts, _cap, lr, lc = bucket_by_tile(
            grid, rows, cols, nrows, ncols, None
        )
        vals = vals[order]
        pr_, pc_ = grid.pr, grid.pc
        if max_k is None:
            max_k = max(int(lc), 1)

        # Per tile: row-sort, then vectorized chunking of every nonempty row
        # into (class, row, start, take) with take <= max_k.
        ladder = _width_ladder(max_k, ladder)
        per_tile = []
        classes = set()
        for t in range(grid.size):
            s0, e0 = starts[t], starts[t + 1]
            r = rows[s0:e0] - (t // pc_) * lr
            c = cols[s0:e0] - (t % pc_) * lc
            v = vals[s0:e0]
            o = np.argsort(r, kind="stable")
            r, c, v = r[o], c[o], v[o]
            ptr = np.searchsorted(r, np.arange(lr + 1))
            deg = ptr[1:] - ptr[:-1]
            nz = np.nonzero(deg)[0]
            d_nz, s_nz = deg[nz], ptr[:-1][nz]
            nchunks = -(-d_nz // max_k)
            rep_row = np.repeat(nz, nchunks)
            rep_deg = np.repeat(d_nz, nchunks)
            rep_start = np.repeat(s_nz, nchunks)
            # chunk index within each row: global arange minus per-row base
            base = np.repeat(
                np.concatenate([[0], np.cumsum(nchunks)])[:-1], nchunks
            )
            chunk = np.arange(len(rep_row)) - base
            take = np.minimum(rep_deg - chunk * max_k, max_k).astype(np.int64)
            start = rep_start + chunk * max_k
            # width-class the chunk (fine ladder: ~1.15x average slot
            # padding; coarse: ~1.34x but fewer bucket sweeps)
            cls = np.searchsorted(ladder, take)
            classes.update(np.unique(cls).tolist())
            per_tile.append((cls, rep_row, start, take, c, v))

        buckets = []
        for b in sorted(classes):
            kb = int(ladder[b])
            nb = max(int((pt[0] == b).sum()) for pt in per_tile)
            nb = max(nb, 1)
            if headroom > 0:
                # reserved re-bucketing slack: every tile of this class
                # gets at least ceil(nb * headroom) FREE rows (padding
                # rowid = lr, inert for the kernels) on top of the
                # occupancy max — the dynamic merge's free-slot pool
                nb += int(np.ceil(nb * headroom))
            bc = np.full((pr_, pc_, nb, kb), lc, np.int32)
            bv = np.zeros((pr_, pc_, nb, kb), vals.dtype)
            br = np.full((pr_, pc_, nb), lr, np.int32)
            for t, (cls, rrow, rstart, rtake, c, v) in enumerate(per_tile):
                i, j = divmod(t, pc_)
                sel = cls == b
                if not sel.any():
                    continue
                srow, sstart, stake = rrow[sel], rstart[sel], rtake[sel]
                m = len(srow)
                # [m, kb] index matrix into the tile's sorted entry arrays
                idx = sstart[:, None] + np.arange(kb)[None, :]
                valid = np.arange(kb)[None, :] < stake[:, None]
                idx = np.where(valid, idx, 0)
                bc[i, j, :m] = np.where(valid, c[idx], lc)
                bv[i, j, :m] = np.where(valid, v[idx], 0)
                br[i, j, :m] = srow
            buckets.append((bc, bv, br))
        return buckets

    @staticmethod
    def from_spmat(
        A: SpParMat, max_k: int | None = None, ladder: str = "fine"
    ) -> "EllParMat":
        """Host conversion from an existing SpParMat (one-time per matrix —
        the kernel-1 pre-pass, like the reference's OptimizeForGraph500,
        SpParMat.cpp:3343). NOTE: reads the tiles back to host; a caller
        that still holds the host COO should use ``from_host_coo``.
        """
        r, c, v = A.to_global_coo()
        return EllParMat.from_host_coo(
            A.grid, r, c, v, A.nrows, A.ncols, max_k=max_k, ladder=ladder
        )

    def reduce(self, sr: Semiring, axis: str, map_fn=None) -> DistVec:
        """Row-wise fold (axis="cols" → row-aligned vector), e.g. degrees
        with ``map_fn=ones``. Column-wise reductions should use the SpParMat
        the ELL was converted from."""
        assert axis == "cols", "EllParMat.reduce supports axis='cols' only"
        return _ell_reduce_rows_jit(self, sr, map_fn)

    def to_host_coo(self):
        """Read the buckets back and reconstruct the global COO, sorted
        by (row, col): ``(rows, cols, vals)`` numpy arrays.  Canonical —
        independent of bucket layout, slot order, or which class a
        sticky incremental merge left a row in — so two EllParMats with
        equal content compare bit-exact (the dynamic-merge acceptance
        check).  A D2H readback: test/tooling path only, never ahead of
        timed launches on readback-poisoned chips (bench.py)."""
        import jax

        lr, lc = self.local_rows, self.local_cols
        rows_all, cols_all, vals_all = [], [], []
        for bc, bv, br in self.buckets:
            bc = np.asarray(jax.device_get(bc))
            bv = np.asarray(jax.device_get(bv))
            br = np.asarray(jax.device_get(br))
            pr_, pc_ = bc.shape[0], bc.shape[1]
            valid = (bc < lc) & (br[..., None] < lr)
            gr = np.broadcast_to(
                (np.arange(pr_, dtype=np.int64)[:, None, None] * lr
                 + br)[..., None],
                bc.shape,
            )
            gc = (
                np.arange(pc_, dtype=np.int64)[None, :, None, None] * lc
                + bc
            )
            rows_all.append(gr[valid])
            cols_all.append(gc[valid])
            vals_all.append(bv[valid])
        if not rows_all:
            return (
                np.empty(0, np.int64), np.empty(0, np.int64),
                np.empty(0, np.float32),
            )
        r = np.concatenate(rows_all)
        c = np.concatenate(cols_all)
        v = np.concatenate(vals_all)
        order = np.argsort(r * np.int64(self.ncols) + c, kind="stable")
        return r[order], c[order], v[order]


def _width_ladder(max_k: int, kind: str = "fine") -> "np.ndarray":
    """Bucket widths clamped to include max_k.

    "fine": 1,2,3,4,6,8,12,... — alternating x1.5 (2^k → 3·2^(k-1)) and
    x4/3 (→ 2^(k+1)) steps, ~1.15x average slot padding.
    "coarse": powers of two — ~1.34x padding but ~half the bucket
    classes (fewer per-sweep gathers; better for 1-lane payloads)."""
    if kind not in ("fine", "coarse"):
        raise ValueError(f"ladder must be 'fine' or 'coarse', got {kind!r}")
    if kind == "coarse":
        widths = [1]
        while widths[-1] < max_k:
            widths.append(widths[-1] * 2)
    else:
        widths = [1, 2]
        while widths[-1] < max_k:
            n = widths[-1]
            widths.append(n * 3 // 2 if (n & (n - 1)) == 0 else n * 4 // 3)
    widths = [w for w in widths if w <= max_k]
    if not widths or widths[-1] != max_k:
        widths.append(max_k)
    return np.asarray(widths, np.int64)


def _bucket_scope(i: int, phase: str):
    """``ell.bucket<i>/<phase>``: degree class ``i`` of a tile's sweep,
    phase ``gather`` / ``fold`` / ``scatter_rows`` (the documented scope
    list is ``models.bfs.BFS_SCOPES``)."""
    return jax.named_scope(f"ell.bucket{i}/{phase}")


def _bucket_fold(sr: Semiring, prods: Array) -> Array:
    if sr.add_kind == "sum":
        return jnp.sum(prods, axis=1)
    if sr.add_kind == "min":
        return jnp.min(prods, axis=1)
    if sr.add_kind == "max":
        return jnp.max(prods, axis=1)
    return lax.reduce(prods, sr.zero(prods.dtype), sr.add, (1,))


def _scatter_rows(sr: Semiring, y: Array, rowids: Array, yb: Array) -> Array:
    """Combine bucket results into y by row id (padding = lr dropped).
    Split hub rows may appear twice within a bucket — every path combines
    duplicates with sr.add (native scatter kinds do; the generic path goes
    through a duplicate-safe segment reduction)."""
    if sr.add_kind == "sum":
        return y.at[rowids].add(yb, mode="drop")
    if sr.add_kind == "min":
        return y.at[rowids].min(yb, mode="drop")
    if sr.add_kind == "max":
        return y.at[rowids].max(yb, mode="drop")
    contrib = segment_reduce(sr, yb, rowids, y.shape[0])
    return sr.add(y, contrib)


def _ell_local_spmv(sr: Semiring, buckets, x: Array, lr: int, lc: int) -> Array:
    """[lr] semiring row fold: per-bucket dense gather+reduce, no big
    scatter (result writes are one slot per bucket row)."""
    zero = sr.zero(x.dtype)
    xpad = jnp.concatenate([x, zero[None]])
    y = None
    out_dtype = None
    for bc, bv, br in buckets:
        g = xpad[jnp.minimum(bc, lc)]  # [nb, kb]
        prods = sr.mul(bv, g)
        yb = _bucket_fold(sr, prods)
        if y is None:
            out_dtype = yb.dtype
            y = jnp.full((lr,), sr.zero(out_dtype), out_dtype)
        y = _scatter_rows(sr, y, br, yb.astype(out_dtype))
    if y is None:
        y = jnp.full((lr,), zero, x.dtype)
    return y


@partial(jax.jit, static_argnames=("sr",))
def dist_spmv_ell(sr: Semiring, E: EllParMat, x: DistVec) -> DistVec:
    """y = E ⊗ x — same schedule as ``dist_spmv``, bucketed-ELL kernel."""
    assert x.length == E.ncols
    x = x.realign("col")
    lr, lc = E.local_rows, E.local_cols
    nb = len(E.buckets)

    def body(xblk, *flat):
        buckets = [tuple(a[0, 0] for a in flat[3 * i : 3 * i + 3]) for i in range(nb)]
        y = _ell_local_spmv(sr, buckets, xblk[0], lr, lc)
        return axis_reduce(sr, y, COL_AXIS)[None]

    flat_args = [a for b in E.buckets for a in b]
    blocks = jax.shard_map(
        body,
        mesh=E.grid.mesh,
        in_specs=(P(COL_AXIS),) + (TILE_SPEC,) * (3 * nb),
        out_specs=P(ROW_AXIS),
    )(x.blocks, *flat_args)
    return DistVec(blocks=blocks, length=E.nrows, align="row", grid=E.grid)


@partial(jax.jit, static_argnames=("sr",))
def dist_spmv_ell_masked(
    sr: Semiring, E: EllParMat, x: DistVec, row_active: DistVec
) -> DistVec:
    assert x.length == E.ncols
    x = x.realign("col")
    row_active = row_active.realign("row")
    lr, lc = E.local_rows, E.local_cols
    nb = len(E.buckets)

    def body(xblk, actblk, *flat):
        buckets = [tuple(a[0, 0] for a in flat[3 * i : 3 * i + 3]) for i in range(nb)]
        y = _ell_local_spmv(sr, buckets, xblk[0], lr, lc)
        y = jnp.where(actblk[0], y, sr.zero(y.dtype))
        return axis_reduce(sr, y, COL_AXIS)[None]

    flat_args = [a for b in E.buckets for a in b]
    blocks = jax.shard_map(
        body,
        mesh=E.grid.mesh,
        in_specs=(P(COL_AXIS), P(ROW_AXIS)) + (TILE_SPEC,) * (3 * nb),
        out_specs=P(ROW_AXIS),
    )(x.blocks, row_active.blocks, *flat_args)
    return DistVec(blocks=blocks, length=E.nrows, align="row", grid=E.grid)


@partial(jax.jit, static_argnames=("sr", "map_fn"))
def _ell_reduce_rows_jit(E: EllParMat, sr: Semiring, map_fn) -> DistVec:
    lr, lc = E.local_rows, E.local_cols
    nb = len(E.buckets)

    def body(*flat):
        buckets = [tuple(a[0, 0] for a in flat[3 * i : 3 * i + 3]) for i in range(nb)]
        y = None
        for bc, bv, br in buckets:
            valid = bc < lc
            v = map_fn(bv) if map_fn is not None else bv
            zero = sr.zero(v.dtype)
            v = jnp.where(valid, v, zero)
            yb = _bucket_fold(sr, v)
            if y is None:
                y = jnp.full((lr,), zero, v.dtype)
            y = _scatter_rows(sr, y, br, yb)
        if y is None:
            probe = (
                map_fn(jnp.zeros((), E.dtype))
                if map_fn is not None
                else jnp.zeros((), E.dtype)
            )
            y = jnp.full((lr,), sr.zero(probe.dtype), probe.dtype)
        return axis_reduce(sr, y, COL_AXIS)[None]

    flat_args = [a for b in E.buckets for a in b]
    blocks = jax.shard_map(
        body,
        mesh=E.grid.mesh,
        in_specs=(TILE_SPEC,) * (3 * nb),
        out_specs=P(ROW_AXIS),
    )(*flat_args)
    return DistVec(blocks=blocks, length=E.nrows, align="row", grid=E.grid)


# --- multi-root (batched) SpMV — frontier-as-matrix, SURVEY §2.3 #7 ---------


def _ell_local_spmm(
    sr: Semiring, buckets, x2: Array, lr: int, lc: int, backend: str
) -> Array:
    """[lr, F] semiring fold of one tile's buckets over a [lc, F]
    dense block — the ONE local gather-contract kernel shared by the
    batched SpMV lanes (W frontier columns) and the round-12 SpMM lane
    (F feature columns).

    Per bucket, ONE gather fetches each neighbor's whole payload row
    (``[rows, kb, F]`` — per-index bound on the target chip, so the
    width rides ~free), then the k axis contracts: backend
    ``"mxu_gather"`` (plus_times only) via a batched ``dot_general``
    ([1, kb] × [kb, F] per bucket row, MXU-eligible); backend
    ``"scatter"`` via the VPU ``_bucket_fold`` + the duplicate-safe
    ``_scatter_rows`` combine (every semiring).  Row slicing keeps the
    gather intermediate under the same byte envelope as the batched
    BFS step (``_bucket_row_slices``; the budget argument is BYTES per
    slot — F lanes × itemsize here where the int8 BFS step passed W).
    """
    F = x2.shape[1]
    zero = sr.zero(x2.dtype)
    xpad = jnp.concatenate([x2, jnp.full((1, F), zero, x2.dtype)])
    y = None
    for i, (bc, bv, br) in enumerate(buckets):
        nb_, kb = bc.shape
        payload = F * max(jnp.dtype(x2.dtype).itemsize, 1)
        for s0, s1 in _bucket_row_slices(nb_, kb, payload):
            with _bucket_scope(i, "gather"):
                g = xpad[jnp.minimum(bc[s0:s1], lc)]  # [rows, kb, F]
            with _bucket_scope(i, "fold"):
                if backend == "mxu_gather":
                    # pad slots: bv holds 0 there (host_build
                    # zero-fills), so the plus_times contraction drops
                    # them exactly
                    out_dtype = jnp.result_type(bv.dtype, x2.dtype)
                    yb = lax.dot_general(
                        bv[s0:s1][:, None, :].astype(out_dtype),
                        g.astype(out_dtype),
                        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=out_dtype,
                    )[:, 0, :]
                else:
                    prods = sr.mul(bv[s0:s1][..., None], g)
                    yb = _bucket_fold(sr, prods)  # [rows, F]
            with _bucket_scope(i, "scatter_rows"):
                if y is None:
                    y = jnp.full((lr, F), sr.zero(yb.dtype), yb.dtype)
                y = _scatter_rows(sr, y, br[s0:s1], yb.astype(y.dtype))
    if y is None:
        y = jnp.full((lr, F), zero, x2.dtype)
    return y


def _ell_local_spmv_multi(sr: Semiring, buckets, x2: Array, lr, lc) -> Array:
    """[lr, W] semiring row fold over a [lc, W] input block.

    Identical structure to ``_ell_local_spmv`` with a trailing batch dim:
    one gathered index fetches W lanes (measured on v5e: W=8 costs the same
    wall time as W=1 — the gather is per-index bound, so the batch rides
    free; this is the kernel-side payoff of multi-source BFS batching).
    Since round 12 this IS the shared gather-contract kernel's scatter
    backend — which also bounds hub-bucket gather intermediates with the
    byte-envelope row slicing the int8 BFS step already had.
    """
    return _ell_local_spmm(sr, buckets, x2, lr, lc, "scatter")


@partial(jax.jit, static_argnames=("sr",))
def dist_spmv_ell_multi(sr: Semiring, E: EllParMat, X) -> "DistMultiVec":
    """Y = E ⊗ X for a DistMultiVec X (W stacked vectors) — the unmasked
    batched kernel: one gathered index feeds all W lanes (payload-width
    nearly free on the target chip), amortizing the per-index gather cost
    W ways for any W-chain iterative app (personalized PageRank, batched
    SSSP sources, BC pivot batches)."""
    from .vec import DistMultiVec

    assert X.length == E.ncols
    X = X.realign("col")
    lr, lc = E.local_rows, E.local_cols
    nb = len(E.buckets)

    def body(xblk, *flat):
        buckets = [
            tuple(a[0, 0] for a in flat[3 * i : 3 * i + 3]) for i in range(nb)
        ]
        y = _ell_local_spmv_multi(sr, buckets, xblk[0], lr, lc)
        return axis_reduce(sr, y, COL_AXIS)[None]

    flat_args = [a for b in E.buckets for a in b]
    blocks = jax.shard_map(
        body,
        mesh=E.grid.mesh,
        in_specs=(P(COL_AXIS),) + (TILE_SPEC,) * (3 * nb),
        out_specs=P(ROW_AXIS),
    )(X.blocks, *flat_args)
    return DistMultiVec(
        blocks=blocks, length=E.nrows, align="row", grid=E.grid
    )


@partial(jax.jit, static_argnames=("sr",))
def dist_spmv_ell_masked_multi(
    sr: Semiring, E: EllParMat, X, row_active
) -> "DistMultiVec":
    """Y = E ⊗ X for a DistMultiVec X (W stacked vectors), with per-lane
    row masking — the batched Graph500 kernel."""
    from .vec import DistMultiVec

    assert X.length == E.ncols
    X = X.realign("col")
    row_active = row_active.realign("row")
    lr, lc = E.local_rows, E.local_cols
    nb = len(E.buckets)

    def body(xblk, actblk, *flat):
        buckets = [
            tuple(a[0, 0] for a in flat[3 * i : 3 * i + 3]) for i in range(nb)
        ]
        y = _ell_local_spmv_multi(sr, buckets, xblk[0], lr, lc)
        with jax.named_scope("ell.reduce"):
            y = jnp.where(actblk[0], y, sr.zero(y.dtype))
            return axis_reduce(sr, y, COL_AXIS)[None]

    flat_args = [a for b in E.buckets for a in b]
    blocks = jax.shard_map(
        body,
        mesh=E.grid.mesh,
        in_specs=(P(COL_AXIS), P(ROW_AXIS)) + (TILE_SPEC,) * (3 * nb),
        out_specs=P(ROW_AXIS),
    )(X.blocks, row_active.blocks, *flat_args)
    return DistMultiVec(
        blocks=blocks, length=E.nrows, align="row", grid=E.grid
    )


def _bucket_row_slices(nb: int, kb: int, W: int,
                       budget_bytes: int = 1 << 32):
    """Static row-slice bounds keeping any [rows, kb, W] gather
    intermediate under ~budget_bytes of int8 payload: XLA materializes
    the gather output of the fold pipeline, so an unsliced 30M-slot hub
    bucket at W=256 would allocate gigabytes — the scale-21 OOM.

    The budget must stay LARGE: slicing scale-20 buckets ~10 ways ran
    4.6x slower (57 vs 264 MTEPS — per-slice scatter and fusion
    overhead); 4GB (= 16M slots at W=256) leaves scale-20 whole, halves
    only the hub buckets, and measured 12% FASTER than unsliced
    (297 MTEPS). The budget scales with W so wider batches keep the same
    byte bound."""
    rows_per = max(budget_bytes // max(kb * max(W, 1), 1), 1)
    return [(s0, min(s0 + rows_per, nb)) for s0 in range(0, nb, rows_per)]


@partial(jax.jit, static_argnames=("ring",))
def _ell_levels_step(E: EllParMat, x8, undiscovered8, ring: bool = False):
    """One batched BFS level over int8 indicator frontiers.

    x8: [pc, lc, W] int8 col-aligned (1 = in frontier); undiscovered8:
    [pr, lr, W] int8 row-aligned (1 = not yet discovered). Returns
    reached8 [pr, lr, W]: 1 where an undiscovered row has a frontier
    in-neighbor. The gather payload is W BYTES per index instead of the
    4W of the parent-carrying kernel — on per-index-bound gather hardware
    with payload-width sensitivity above ~256B this is the difference
    between ~0.45s and ~1.6s per level at scale 20 x W=256.
    """
    lr, lc = E.local_rows, E.local_cols
    nb = len(E.buckets)

    def body(xblk, ublk, *flat):
        buckets = [
            tuple(a[0, 0] for a in flat[3 * i : 3 * i + 3]) for i in range(nb)
        ]
        x = xblk[0]  # [lc, W] int8
        W = x.shape[1]
        xpad = jnp.concatenate([x, jnp.zeros((1, W), jnp.int8)])
        y = jnp.zeros((lr, W), jnp.int8)
        for i, (bc, _bv, br) in enumerate(buckets):
            nb_, kb = bc.shape
            for s0, s1 in _bucket_row_slices(nb_, kb, W):
                with _bucket_scope(i, "gather"):
                    # [rows, kb, W] int8
                    g = xpad[jnp.minimum(bc[s0:s1], lc)]
                with _bucket_scope(i, "fold"):
                    yb = jnp.max(g, axis=1)  # [rows, W]
                with _bucket_scope(i, "scatter_rows"):
                    y = y.at[br[s0:s1]].max(yb, mode="drop")
        with jax.named_scope("ell.reduce"):
            y = jnp.minimum(y, ublk[0])  # only undiscovered rows fire
            if ring:
                # the carousel schedule: neighbor ppermute rotation over
                # the 'c' mesh axis (COL_AXIS — same axis the pmax path
                # reduces) instead of the fused all-reduce
                from ..semiring import SELECT2ND_MAX
                from .collectives import axis_ring_reduce

                return axis_ring_reduce(SELECT2ND_MAX, y, COL_AXIS)[None]
            return lax.pmax(y, COL_AXIS)[None]

    flat_args = [a for b in E.buckets for a in b]
    return jax.shard_map(
        body,
        mesh=E.grid.mesh,
        in_specs=(P(COL_AXIS), P(ROW_AXIS)) + (TILE_SPEC,) * (3 * nb),
        out_specs=P(ROW_AXIS),
        # the ring fold provably replicates over "c" (a full rotation
        # visits every neighbor) but shard_map cannot infer that through
        # ppermute — same situation as DistVec.realign; the default pmax
        # path keeps the check on
        check_vma=not ring,
    )(x8, undiscovered8, *flat_args)


@partial(jax.jit, static_argnames=())
def _ell_parents_from_levels(E: EllParMat, levels_col, levels_row):
    """Parent reconstruction: for every (row, root) pick the max-id
    in-neighbor whose level is exactly level(row)-1.

    levels_col: [pc, lc, W] int8 (col-aligned levels, -1 undiscovered);
    levels_row: [pr, lr, W]. One W-byte-payload gather pass over the
    matrix — the whole-search parent information the compact BFS loop
    deliberately did not carry.
    """
    lr, lc = E.local_rows, E.local_cols
    nb = len(E.buckets)

    def body(lcb, lrb, *flat):
        buckets = [
            tuple(a[0, 0] for a in flat[3 * i : 3 * i + 3]) for i in range(nb)
        ]
        lvl_c = lcb[0]  # [lc, W] int8
        W = lvl_c.shape[1]
        lvl_r = lrb[0]  # [lr, W] int8
        cpad = jnp.concatenate([lvl_c, jnp.full((1, W), -1, jnp.int8)])
        j = lax.axis_index(COL_AXIS)
        col_base = j * lc
        y = jnp.full((lr, W), -1, jnp.int32)
        want = jnp.where(
            lvl_r > 0, lvl_r - 1, jnp.int8(-2)
        )  # rows at level 0 (roots) or undiscovered never match
        for i, (bc, _bv, br) in enumerate(buckets):
            nb_, kb = bc.shape
            # int32 candidates: half the byte budget of the int8 step
            for s0, s1 in _bucket_row_slices(nb_, kb, W,
                                             budget_bytes=1 << 31):
                with _bucket_scope(i, "gather"):
                    safe = jnp.minimum(bc[s0:s1], lc)
                    g = cpad[safe]  # [rows, kb, W] int8 neighbor levels
                with _bucket_scope(i, "fold"):
                    brs = br[s0:s1]
                    wantb = want[jnp.minimum(brs, lr - 1)][:, None, :]
                    gid = (col_base + safe).astype(jnp.int32)[:, :, None]
                    cand = jnp.where(g == wantb, gid, -1)  # [rows, kb, W]
                    yb = jnp.max(cand, axis=1)  # [rows, W]
                with _bucket_scope(i, "scatter_rows"):
                    y = y.at[brs].max(yb, mode="drop")
        with jax.named_scope("ell.reduce"):
            return lax.pmax(y, COL_AXIS)[None]

    flat_args = [a for b in E.buckets for a in b]
    return jax.shard_map(
        body,
        mesh=E.grid.mesh,
        in_specs=(P(COL_AXIS), P(ROW_AXIS)) + (TILE_SPEC,) * (3 * nb),
        out_specs=P(ROW_AXIS),
    )(levels_col, levels_row, *flat_args)


# --- budgeted union-frontier sparse step (direction optimization for the
# BATCHED search; ≈ the top-down regime of DirOptBFS applied to all W
# roots at once) -------------------------------------------------------------


def build_csc_companion(grid: Grid, rows, cols, nrows: int, ncols: int):
    """Host build of per-tile CSC structure arrays for column walks:
    (indptr [pr, pc, lc+1], rowidx [pr, pc, cap]) int32, cap = max tile
    nnz. The EllParMat's row buckets cannot walk COLUMNS; sparse
    union-frontier steps need exactly that (the reference's SpImpl CSC
    kernels, SpImpl.cpp:345-600)."""
    indptr, rowidx = build_csc_companion_host(grid, rows, cols, nrows, ncols)
    return upload_csc_companion(grid, indptr, rowidx)


def upload_csc_companion(grid: Grid, indptr, rowidx):
    """Upload pre-built host CSC arrays (``build_csc_companion_host``)."""
    sh = grid.tile_sharding()
    return (
        jax.device_put(jnp.asarray(indptr), sh),
        jax.device_put(jnp.asarray(rowidx), sh),
    )


def build_csr_companion(grid: Grid, rows, cols, nrows: int, ncols: int):
    """Row-major twin of ``build_csc_companion``: (indptr [pr, pc, lr+1],
    colidx [pr, pc, cap]) — per-tile ROW walks for the bottom-up BFS
    regime (``models/bfs.py`` "bu" tiers). For a SYMMETRIC matrix on a
    1x1 grid the CSC companion arrays are identical and may be reused."""
    indptr, colidx = build_csr_companion_host(grid, rows, cols, nrows, ncols)
    return upload_csc_companion(grid, indptr, colidx)


def build_csr_companion_host(grid: Grid, rows, cols, nrows: int, ncols: int):
    """Host-only half of ``build_csr_companion`` (numpy in, numpy out)."""
    return _companion_host(grid, rows, cols, nrows, ncols, major="row")


def build_csc_companion_host(grid: Grid, rows, cols, nrows: int, ncols: int):
    """Host-only half of ``build_csc_companion`` (numpy in, numpy out) —
    serializable for the bench parent → timing-children .npz handoff."""
    return _companion_host(grid, rows, cols, nrows, ncols, major="col")


def _companion_host(grid, rows, cols, nrows, ncols, *, major):
    """Shared per-tile walk-structure builder: sort each tile's tuples by
    the major axis, indptr over that axis, minor indices padded with the
    minor block size as the inert sentinel."""
    import numpy as np

    from .spmat import bucket_by_tile

    rows, cols, order, counts, starts, _cap, lr, lc = bucket_by_tile(
        grid, rows, cols, nrows, ncols, None
    )
    pr_, pc_ = grid.pr, grid.pc
    cap = max(int(counts.max()), 1)
    lmaj, lmin = (lr, lc) if major == "row" else (lc, lr)
    indptr = np.zeros((pr_, pc_, lmaj + 1), np.int32)
    minidx = np.full((pr_, pc_, cap), lmin, np.int32)
    for t in range(grid.size):
        i, j = divmod(t, pc_)
        s0, e0 = starts[t], starts[t + 1]
        r = rows[s0:e0] - i * lr
        c = cols[s0:e0] - j * lc
        maj, mino = (r, c) if major == "row" else (c, r)
        o = np.argsort(maj, kind="stable")
        indptr[i, j] = np.searchsorted(maj[o], np.arange(lmaj + 1))
        minidx[i, j, : e0 - s0] = mino[o]
    return indptr, minidx


@partial(jax.jit, static_argnames=("frontier_capacity", "edge_capacity"))
def _ell_union_sparse_step(
    E: EllParMat, csc_indptr, csc_rowidx, x8, undiscovered8,
    frontier_capacity: int, edge_capacity: int,
):
    """One batched BFS level touching ONLY the union-frontier columns.

    The dense level costs ~nnz gathers regardless of frontier size; when
    the UNION of all W frontiers is small (first levels, straggler tail),
    walking just those columns' edges costs ~edge_capacity instead. The
    caller guarantees the budgets (on-device cond in bfs_batch_compact).
    Semantics identical to _ell_levels_step.
    """
    from ..ops.segment import expand_ranges

    lr, lc = E.local_rows, E.local_cols

    def body(ipt, ridx, xblk, ublk):
        indptr = ipt[0, 0]  # [lc+1]
        rowid = ridx[0, 0]  # [cap]
        x = xblk[0]  # [lc, W] int8
        W = x.shape[1]
        act = jnp.max(x, axis=1) > 0  # [lc] union frontier
        # compact active local columns into F slots
        pos = jnp.cumsum(act.astype(jnp.int32)) - 1
        scatter = jnp.where(act, pos, frontier_capacity)
        fcols = (
            jnp.full((frontier_capacity,), lc, jnp.int32)
            .at[scatter]
            .set(jnp.arange(lc, dtype=jnp.int32), mode="drop")
        )
        ipt_pad = jnp.concatenate([indptr, indptr[-1:]])
        deg = jnp.where(
            fcols < lc, ipt_pad[fcols + 1] - ipt_pad[fcols], 0
        )
        owner, offset, valid, _ = expand_ranges(deg, edge_capacity)
        src_col = fcols[owner]  # local col of this edge
        slot = jnp.minimum(ipt_pad[jnp.minimum(src_col, lc)] + offset,
                           rowid.shape[0] - 1)
        tgt_row = jnp.where(valid, rowid[slot], lr)
        # per-root frontier value of the edge's source column: [Ecap, W]
        xpad = jnp.concatenate([x, jnp.zeros((1, W), jnp.int8)])
        contrib = xpad[jnp.minimum(src_col, lc)]
        contrib = jnp.where(valid[:, None], contrib, 0)
        y = jnp.zeros((lr, W), jnp.int8).at[tgt_row].max(
            contrib, mode="drop"
        )
        y = jnp.minimum(y, ublk[0])
        return lax.pmax(y, COL_AXIS)[None]

    return jax.shard_map(
        body,
        mesh=E.grid.mesh,
        in_specs=(TILE_SPEC, TILE_SPEC, P(COL_AXIS), P(ROW_AXIS)),
        out_specs=P(ROW_AXIS),
    )(csc_indptr, csc_rowidx, x8, undiscovered8)
