"""3D (communication-avoiding) distribution — ≈ CommGrid3D / SpParMat3D /
Mult_AnXBn_SUMMA3D.

The reference's 3D grid factors p = layers × (pr × pc): each layer runs 2D
SUMMA on a column- (or row-) slice of the matrix and partial products are
combined across the ``fiberWorld`` (``CommGrid3D.h:44-80``,
``SpParMat3D.h:43-92``, ``ParFriends.h:2919-3213``). The payoff is
communication-avoidance: per-layer broadcast volume shrinks L-fold at the
cost of L-fold result replication before the fiber reduce.

TPU-native mapping:

* Grid3D = a 3-axis ``Mesh`` ("l", "r", "c"); the fiberWorld is just the
  ``"l"`` axis name.
* SpParMat3D stores tiles as ``[L, pr, pc, cap]`` arrays — ONE pytree for
  all layers, like SpParMat's stacked tiles.
* Splits are LOCAL, exactly as the reference's ``ColSplit`` conversion
  (``SpParMat3D.cpp:74-145``): layer l holds the l-th slice of every 2D
  tile's local columns (col-split) or rows (row-split). Local splitting
  keeps every piece's owner computable without global re-bucketing — the
  same reason the reference chose it.
* SUMMA3D = per-layer 2D SUMMA (all_gathers over "c"/"r" act within a
  layer automatically — axis names ARE the subcommunicators) + an
  ``all_to_all`` over "l" of locally-col-split pieces + a compacting merge:
  the fiber reduce-scatter of ``ParFriends.h:3119-3180``.

Square layer grids and square matrices keep A's col-split aligned with B's
row-split over the contraction index (lr == lc), mirroring the reference's
usage (HipMCL 3D runs on square grids).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..ops.compressed import CSR
from ..ops.spgemm import expand as esc_expand
from ..ops.tuples import SpTuples
from ..semiring import Semiring
from .grid import COL_AXIS, LAYER_AXIS, ROW_AXIS, Grid

Array = jax.Array

TILE3_SPEC = P(LAYER_AXIS, ROW_AXIS, COL_AXIS)


@dataclasses.dataclass(frozen=True)
class Grid3D:
    """layers × pr × pc device mesh (≈ CommGrid3D)."""

    mesh: Mesh

    @staticmethod
    def make(layers: int, pr: int, pc: int, devices=None) -> "Grid3D":
        if devices is None:
            devices = jax.devices()[: layers * pr * pc]
        if len(devices) < layers * pr * pc:
            raise ValueError(
                f"need {layers * pr * pc} devices, have {len(devices)}"
            )
        arr = np.asarray(devices[: layers * pr * pc]).reshape(layers, pr, pc)
        return Grid3D(mesh=Mesh(arr, (LAYER_AXIS, ROW_AXIS, COL_AXIS)))

    @property
    def layers(self) -> int:
        return self.mesh.shape[LAYER_AXIS]

    @property
    def pr(self) -> int:
        return self.mesh.shape[ROW_AXIS]

    @property
    def pc(self) -> int:
        return self.mesh.shape[COL_AXIS]

    def local_rows(self, nrows: int) -> int:
        return -(-nrows // self.pr)

    def local_cols(self, ncols: int) -> int:
        return -(-ncols // self.pc)

    def tile_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, TILE3_SPEC)

    def __hash__(self):
        return hash((Grid3D, self.mesh))

    def __eq__(self, other):
        return isinstance(other, Grid3D) and self.mesh == other.mesh


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["rows", "cols", "vals", "nnz"],
    meta_fields=["nrows", "ncols", "split", "grid"],
)
@dataclasses.dataclass(frozen=True)
class SpParMat3D:
    """3D-distributed sparse matrix (≈ SpParMat3D<IT,NT,DER>).

    rows/cols: int32[L, pr, pc, cap] LAYER-LOCAL tile indices; a col-split
    layer tile spans [local_rows × local_cols/L], a row-split tile
    [local_rows/L × local_cols]. nrows/ncols are the GLOBAL matrix dims.
    """

    rows: Array
    cols: Array
    vals: Array
    nnz: Array
    nrows: int
    ncols: int
    split: str  # "col" | "row"
    grid: Grid3D

    @property
    def capacity(self) -> int:
        return self.rows.shape[3]

    @property
    def tile_rows(self) -> int:
        lr = self.grid.local_rows(self.nrows)
        return lr // self.grid.layers if self.split == "row" else lr

    @property
    def tile_cols(self) -> int:
        lc = self.grid.local_cols(self.ncols)
        return lc // self.grid.layers if self.split == "col" else lc

    def getnnz(self) -> Array:
        return jnp.sum(self.nnz)

    def local_tile(self, rows, cols, vals, nnz) -> SpTuples:
        return SpTuples(
            rows=rows[0, 0, 0], cols=cols[0, 0, 0], vals=vals[0, 0, 0],
            nnz=nnz[0, 0, 0], nrows=self.tile_rows, ncols=self.tile_cols,
        )

    # --- host construction / extraction ------------------------------------

    @staticmethod
    def from_global_coo(
        grid: Grid3D, rows, cols, vals, nrows, ncols, split: str = "col",
        capacity: int | None = None,
    ) -> "SpParMat3D":
        """Bucket global tuples by (layer, tile) with LOCAL split semantics:
        2D tile (i,j) = (r//lr, c//lc); layer = (local col)//(lc/L) for
        col-split, (local row)//(lr/L) for row-split."""
        assert split in ("col", "row")
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        vals = np.asarray(vals)
        L = grid.layers
        lr, lc = grid.local_rows(nrows), grid.local_cols(ncols)
        assert (lc if split == "col" else lr) % L == 0, (
            "local dim must divide evenly over layers"
        )
        ti, tj = rows // lr, cols // lc
        lrow, lcol = rows - ti * lr, cols - tj * lc
        if split == "col":
            w = lc // L
            layer, lcol = lcol // w, lcol % w
            tr, tc = lr, w
        else:
            w = lr // L
            layer, lrow = lrow // w, lrow % w
            tr, tc = w, lc
        flat = ((layer * grid.pr + ti) * grid.pc + tj).astype(np.int64)
        order = np.argsort(flat, kind="stable")
        flat, lrow, lcol, vals_s = flat[order], lrow[order], lcol[order], vals[order]
        counts = np.bincount(flat, minlength=L * grid.pr * grid.pc)
        cap = int(capacity) if capacity else max(int(counts.max()), 1)
        R = np.full((L, grid.pr, grid.pc, cap), tr, np.int32)
        C = np.full((L, grid.pr, grid.pc, cap), tc, np.int32)
        V = np.zeros((L, grid.pr, grid.pc, cap), vals.dtype)
        starts = np.concatenate([[0], np.cumsum(counts)])
        for t in range(L * grid.pr * grid.pc):
            l_, rem = divmod(t, grid.pr * grid.pc)
            i, j = divmod(rem, grid.pc)
            s, e = starts[t], starts[t + 1]
            R[l_, i, j, : e - s] = lrow[s:e]
            C[l_, i, j, : e - s] = lcol[s:e]
            V[l_, i, j, : e - s] = vals_s[s:e]
        sh = grid.tile_sharding()
        return SpParMat3D(
            rows=jax.device_put(jnp.asarray(R), sh),
            cols=jax.device_put(jnp.asarray(C), sh),
            vals=jax.device_put(jnp.asarray(V), sh),
            nnz=jax.device_put(
                jnp.asarray(counts.reshape(L, grid.pr, grid.pc), jnp.int32), sh
            ),
            nrows=int(nrows), ncols=int(ncols), split=split, grid=grid,
        )

    def to_global_coo(self):
        """Inverse of ``from_global_coo`` (host, tests)."""
        L = self.grid.layers
        lr = self.grid.local_rows(self.nrows)
        lc = self.grid.local_cols(self.ncols)
        tr, tc = self.tile_rows, self.tile_cols
        R = np.asarray(self.rows)
        C = np.asarray(self.cols)
        V = np.asarray(self.vals)
        N = np.asarray(self.nnz)
        out = ([], [], [])
        for l_ in range(L):
            for i in range(self.grid.pr):
                for j in range(self.grid.pc):
                    m = R[l_, i, j] < tr
                    assert m.sum() == N[l_, i, j]
                    rr = R[l_, i, j, m].astype(np.int64)
                    cc = C[l_, i, j, m].astype(np.int64)
                    if self.split == "col":
                        gr = i * lr + rr
                        gc = j * lc + l_ * tc + cc
                    else:
                        gr = i * lr + l_ * tr + rr
                        gc = j * lc + cc
                    out[0].append(gr)
                    out[1].append(gc)
                    out[2].append(V[l_, i, j, m])
        return tuple(np.concatenate(x) for x in out)

    def to_dense(self) -> np.ndarray:
        r, c, v = self.to_global_coo()
        out = np.zeros((self.nrows, self.ncols), v.dtype)
        np.add.at(out, (r, c), v)
        return out

    # --- 2D <-> 3D conversions (on-device; see module-level functions) ------

    @staticmethod
    def from_spmat(
        A, grid3: "Grid3D", split: str = "col", **kw
    ) -> "SpParMat3D":
        """2D SpParMat → 3D (≈ ``SpParMat3D(SpParMat&)``)."""
        return spmat3d_from_spmat(A, grid3, split, **kw)

    def to_spmat(self, grid2, **kw):
        """3D → 2D SpParMat (≈ the layermat readback conversion)."""
        return spmat_from_spmat3d(self, grid2, **kw)

    def shrink_to_fit(self, pow2: bool = True) -> "SpParMat3D":
        """Host helper: truncate slot capacity to the max tile nnz (pieces
        from ``col_split`` are front-compacted, so slicing is safe)."""
        need = max(int(np.max(np.asarray(self.nnz))), 1)
        if pow2:
            need = 1 << (need - 1).bit_length()
        need = min(need, self.capacity)
        if need == self.capacity:
            return self
        return dataclasses.replace(
            self,
            rows=self.rows[..., :need],
            cols=self.cols[..., :need],
            vals=self.vals[..., :need],
        )

    # --- local column split / concat (3D phased execution) -----------------

    def col_split(self, nsplits: int) -> list["SpParMat3D"]:
        """Phase splitter for the 3D product (≈ the per-phase ColSplit of
        ``MemEfficientSpGEMM3D``, ParFriends.h:3215-3712).

        Row-split matrices only (B's orientation in C = A ⊗ B). The split
        is STRIDED per layer window: with w = tile_cols/L, piece s takes
        sub-window [s·w/nsplits, (s+1)·w/nsplits) of EVERY layer window, so
        the phase outputs of SUMMA3D land fiber-aligned and concatenate
        without inter-layer movement.
        """
        assert self.split == "row", "col_split phases a row-split operand"
        L = self.grid.layers
        tc = self.tile_cols
        assert tc % (L * nsplits) == 0, (
            f"tile cols {tc} must divide by layers*phases = {L * nsplits}"
        )
        assert self.ncols % nsplits == 0
        return list(_col_split3d_jit(self, nsplits))

    @staticmethod
    def col_concatenate(mats: list["SpParMat3D"]) -> "SpParMat3D":
        """Stitch ``col_split`` pieces / SUMMA3D phase outputs back.

        col-split pieces (phase OUTPUTS): per-layer windows are separate
        array dimensions, so stitching is a plain tile-column offset.
        row-split pieces (inverting ``col_split``): the strided interleave
        is undone per layer window.
        """
        L = mats[0].grid.layers
        tcs = [m.tile_cols for m in mats]
        tc_out = sum(tcs)
        if mats[0].split == "row":
            # inverse of the strided col_split: equal windows required
            assert len(set(tcs)) == 1, "row-split concat needs equal widths"
        arrays = {"rows": [], "cols": [], "vals": []}
        nnz = None
        off = 0
        for s, (m, tcp) in enumerate(zip(mats, tcs)):
            valid = m.rows < m.tile_rows
            if m.split == "col":
                newcol = m.cols + off  # cumulative: pieces may differ in width
            else:
                wp = tcp // L
                w_out = tc_out // L
                newcol = (m.cols // wp) * w_out + s * wp + (m.cols % wp)
            off += tcp
            arrays["rows"].append(m.rows)
            arrays["cols"].append(jnp.where(valid, newcol, tc_out))
            arrays["vals"].append(m.vals)
            nnz = m.nnz if nnz is None else nnz + m.nnz
        return dataclasses.replace(
            mats[0],
            rows=jnp.concatenate(arrays["rows"], axis=3),
            cols=jnp.concatenate(arrays["cols"], axis=3),
            vals=jnp.concatenate(arrays["vals"], axis=3),
            nnz=nnz,
            ncols=sum(m.ncols for m in mats),
        )


@partial(jax.jit, static_argnames=("nsplits",))
def _col_split3d_jit(mat: SpParMat3D, nsplits: int):
    """Strided per-layer-window selection (see ``col_split`` docstring),
    batched over the [L, pr, pc] tile axes with one argsort compaction
    along the slot axis per piece."""
    tr, tc = mat.tile_rows, mat.tile_cols
    L = mat.grid.layers
    w = tc // L  # per-layer output window in the contraction product
    wp = w // nsplits
    valid = mat.rows < tr
    l_win = mat.cols // w
    within = mat.cols % w
    outs = []
    for s in range(nsplits):
        keep = valid & (within // wp == s)
        newcol = l_win * wp + (within % wp)
        piece_tc = L * wp
        # kept entries first (original order), dropped entries pushed back
        order = jnp.argsort(jnp.where(keep, 0, 1), axis=3, stable=True)
        gather = lambda x: jnp.take_along_axis(x, order, axis=3)
        outs.append(
            dataclasses.replace(
                mat,
                rows=gather(jnp.where(keep, mat.rows, tr)),
                cols=gather(jnp.where(keep, newcol, piece_tc)),
                vals=gather(jnp.where(keep, mat.vals, 0)),
                nnz=jnp.sum(keep, axis=3).astype(jnp.int32),
                ncols=mat.ncols // nsplits,
            )
        )
    return tuple(outs)


def mem_efficient_spgemm3d(
    sr: Semiring,
    A: SpParMat3D,
    B: SpParMat3D,
    phases: int,
    *,
    slack: float = 1.05,
    prune_fn=None,
) -> SpParMat3D:
    """Phased 3D SUMMA: C = A ⊗ B over column chunks of B.

    Reference: ``MemEfficientSpGEMM3D`` (ParFriends.h:3215-3712) — the 3D
    expansion path of HipMCL with layers > 1: per phase, one SUMMA3D over a
    column slice of the row-split B, optional prune hook, outputs
    concatenated. A's gathers repeat per phase (the memory/time trade).
    """
    L = B.grid.layers
    assert B.split == "row", (
        "mem_efficient_spgemm3d phases the row-split operand B; got "
        f"split={B.split!r} (build B with split='row')"
    )

    def _splittable(ph: int) -> bool:
        return B.tile_cols % (L * ph) == 0 and B.ncols % ph == 0

    if phases > 1 and not _splittable(phases):
        # Snap DOWN to the nearest valid phase count: running unphased would
        # discard the caller's memory bound entirely, while a smaller valid
        # split preserves most of it.
        snapped = max(
            (ph for ph in range(phases - 1, 0, -1) if _splittable(ph)),
            default=1,
        )
        import warnings

        warnings.warn(
            f"mem_efficient_spgemm3d: tile_cols={B.tile_cols} / "
            f"ncols={B.ncols} not splittable into {phases} phases with "
            f"{L} layers (needs tile_cols % (layers*phases) == 0 and "
            f"ncols % phases == 0); snapping to {snapped} phases",
            stacklevel=2,
        )
        phases = snapped
    if phases <= 1:
        C = spgemm3d(sr, A, B, slack)
        return prune_fn(C) if prune_fn is not None else C
    outs = []
    for Bs in B.col_split(phases):
        # phase pieces inherit B's full slot capacity; truncate so each
        # SUMMA3D gathers phase-sized arrays (the point of phasing)
        C = spgemm3d(sr, A, Bs.shrink_to_fit(), slack)
        if prune_fn is not None:
            C = prune_fn(C)
        outs.append(C)
    return SpParMat3D.col_concatenate(outs)


def _fiber_exchange(partial_c: SpTuples, L: int, w_out: int,
                    piece_capacity: int, *, sort_pieces: bool = False):
    """Fiber exchange of one layer's partial product: split its local
    cols into L pieces of width ``w_out`` (rebased to piece-local
    columns) and ``all_to_all`` them over the layer axis.  The fiber
    Alltoallv of ``ParFriends.h:3119-3180``, shared by the ESC and
    windowed 3D kernels.  Returns (received piece runs — one sorted or
    order-preserved [piece_capacity] SpTuples per source layer — and
    the piece overflow: the max count of entries a piece had to DROP
    to fit ``piece_capacity``; zero means the exchange was lossless).
    Callers combine the runs with ``_fiber_merge``.

    ``sort_pieces=True`` row-major-sorts each OUTGOING piece before the
    exchange — the pre-sort the ``merge="runs"`` tier needs when the
    producing kernel's partial is not already (row, col)-sorted (ESC
    stage chunks, 2D-windowed dot2d chunk order).  L piece-local sorts
    are strictly cheaper than the one concat-sized sort they replace,
    and they ride the exchange side where the partial is still
    column-partitioned."""
    lr = partial_c.nrows
    piece_arrays = []
    worst = jnp.int32(0)
    for l_ in range(L):
        lo = l_ * w_out
        keep = (
            partial_c.valid_mask()
            & (partial_c.cols >= lo)
            & (partial_c.cols < lo + w_out)
        )
        nkeep = jnp.sum(keep).astype(jnp.int32)
        worst = jnp.maximum(worst, nkeep - piece_capacity)
        sel = partial_c._select(keep).with_capacity(piece_capacity)
        cols = jnp.where(sel.valid_mask(), sel.cols - lo, w_out)
        piece = SpTuples(
            rows=sel.rows, cols=cols, vals=sel.vals, nnz=sel.nnz,
            nrows=lr, ncols=w_out,
        )
        if sort_pieces:
            piece = piece.sort_rowmajor()
        piece_arrays.append((piece.rows, piece.cols, piece.vals,
                             piece.nnz))

    stacked = tuple(
        jnp.stack([pa[k] for pa in piece_arrays])
        for k in range(4)
    )  # each [L, piece_capacity] / [L]
    received = tuple(
        lax.all_to_all(x, LAYER_AXIS, split_axis=0, concat_axis=0)
        for x in stacked
    )
    runs = [
        SpTuples(
            rows=received[0][l_], cols=received[1][l_],
            vals=received[2][l_], nnz=received[3][l_],
            nrows=lr, ncols=w_out,
        )
        for l_ in range(L)
    ]
    return runs, worst


#: Valid fiber-reduce combine tiers (docs/spgemm.md "merge tiers").
MERGE_TIERS = ("sort", "runs", "hash")

#: Probe rounds of the hash merge tier before the counted overflow
#: fallback kicks in (load factor <= 0.25 via ``hash_table_capacity``
#: puts the per-element exhaustion odds near alpha^k ~ 1e-10 at this
#: budget — the fallback is a safety net, not a steady-state path).
HASH_MERGE_PROBES = 16


def _fiber_merge(
    sr: Semiring,
    runs: list[SpTuples],
    out_capacity: int,
    merge: str,
):
    """Combine the received fiber piece runs into one compacted tile —
    the merge half of the fiber reduce, in the selected tier:

      ``sort``  concat + full ``lax.sort`` compact (the classic path);
      ``runs``  k-way rank-space union of the (pre)sorted runs
                (``ops.spgemm.merge_sorted_runs``) + sort-free compact;
      ``hash``  bounded open-addressing accumulate
                (``ops.spgemm.hash_merge``) — unsorted output order.

    Returns ``(out, merge_over, hash_over)``: ``merge_over`` > 0 means
    the distinct-key count exceeded ``out_capacity`` (truncation),
    ``hash_over`` > 0 means the hash table failed to place entries
    (the caller MUST fall back to a sorted tier — the output is
    incomplete)."""
    from ..ops.spgemm import hash_merge, hash_table_capacity, \
        merge_sorted_runs

    if merge == "runs":
        merged = merge_sorted_runs(runs)
        out, distinct = merged.compact_counted(
            sr, capacity=out_capacity, assume_sorted=True
        )
        return out, distinct - out_capacity, jnp.int32(0)
    if merge == "hash":
        out, hash_over, distinct = hash_merge(
            sr, SpTuples.concat(runs), out_capacity=out_capacity,
            table_capacity=hash_table_capacity(out_capacity),
            n_probes=HASH_MERGE_PROBES,
        )
        return out, distinct - out_capacity, hash_over
    assert merge == "sort", merge
    out, distinct = SpTuples.concat(runs).compact_counted(
        sr, capacity=out_capacity
    )
    return out, distinct - out_capacity, jnp.int32(0)


def _merge_heuristic(sr: Semiring, L: int, expansion_ratio: float,
                     pieces_sorted: bool) -> str:
    """The merge tier where no argument names one: ``runs`` when the
    pieces arrive already sorted — the windowed
    tiers' structural freebie (no sort anywhere in the reduce; the
    r13 capture's 1.87x) always beats speculating on the hash table;
    ``hash`` for UNSORTED producers at high layer counts with heavy
    cross-layer collision (expansion_ratio ≈ total piece slots /
    distinct bound), where the open-addressing combine's O(nnz) beats
    both the pre-sorts and the one concat sort; ``sort`` otherwise
    (unsorted producers at low L — the r13 scale-12 sweep measured
    the piece pre-sort + union LOSING to the one concat sort at L=2).
    CPU-mesh-measured thresholds; a TPU re-measure is an open
    ROADMAP item."""
    from ..ops.spgemm import scatter_combine_for

    if pieces_sorted:
        return "runs"
    if scatter_combine_for(sr) is not None and (
        L >= 4 and expansion_ratio >= 4.0
    ):
        return "hash"
    return "sort"


@partial(
    jax.jit,
    static_argnames=("sr", "flop_capacity", "out_capacity",
                     "piece_capacity", "ring", "merge"),
)
def summa3d_spgemm(
    sr: Semiring,
    A: SpParMat3D,
    B: SpParMat3D,
    *,
    flop_capacity: int,
    out_capacity: int,
    piece_capacity: int,
    ring: bool = False,
    merge: str = "sort",
) -> tuple[SpParMat3D, Array]:
    """C (col-split) = A (col-split) ⊗ B (row-split) over the 3D mesh.

    Reference: ``Mult_AnXBn_SUMMA3D`` (ParFriends.h:2919-3213). Layer l
    multiplies its contraction slice with a p-stage 2D SUMMA (gathers ride
    the within-layer "c"/"r" subcommunicators), the L partial products are
    exchanged as locally-col-split pieces over the fiber axis "l"
    (``all_to_all`` = the fiber Alltoallv at :3119-3180), and each layer
    merges its received pieces.

    ``flop_capacity``: one stage's expansion per tile; ``piece_capacity``:
    one outgoing fiber piece per tile; ``out_capacity``: final tile nnz.

    ``ring=True`` runs each layer's 2D SUMMA as the STAGE-PIPELINED
    carousel (``spgemm._carousel_stages``: two-slot neighbor-rotation
    buffers on the within-layer joint (row, col) axis, stage s+1's
    ppermute issued before stage s's expand consumes its tiles) instead
    of the up-front all_gathers — O(2·tile) sparse operand memory per
    layer, the r9 schedule the 3D tier was missing.  ``merge`` picks
    the fiber-reduce combine tier (``MERGE_TIERS``; ESC stage chunks
    are unsorted, so ``"runs"`` pre-sorts each outgoing piece).

    Returns ``(C, overflow[3])``: the per-device max of (fiber piece
    drop, merge distinct-keys − out_capacity, hash placement
    overflow) — all ≤ 0 means the product is exact; a positive hash
    overflow means the CALLER must rerun through a sorted tier.
    """
    assert A.split == "col" and B.split == "row"
    assert A.grid == B.grid and A.ncols == B.nrows
    assert merge in MERGE_TIERS, merge
    grid = A.grid
    p = grid.pr
    assert grid.pr == grid.pc, "SUMMA3D requires square layer grids"
    L = grid.layers
    lr = A.tile_rows  # full local rows of C
    lcB = B.tile_cols  # full local cols of C partials
    assert A.tile_cols == B.tile_rows, "contraction blocking mismatch"
    assert lcB % L == 0
    w_out = lcB // L
    if obs.ENABLED:
        # trace-time (jitted fn): counts (re)traces per static config
        obs.count("trace.summa3d_spgemm", ring=ring, merge=merge)
        if ring and p > 1:
            obs.count("spgemm.pipeline.stages_overlapped", p - 1)

    def body(ar, ac, av, an, br, bc, bv, bn):
        from .spgemm import _carousel_stages, _gather_stage_tiles

        a_mine = A.local_tile(ar, ac, av, an)
        b_mine = B.local_tile(br, bc, bv, bn)
        if ring:
            # per-layer carousel: the joint (row, col) ppermute acts
            # within each layer automatically (axis names ARE the
            # subcommunicators), so the 2D rotation schedule lifts to
            # the 3-axis mesh unchanged
            chunks = [
                esc_expand(sr, a_cur, CSR.from_tuples(b_cur),
                           flop_capacity)
                for _, a_cur, b_cur in _carousel_stages(
                    a_mine, b_mine, p
                )
            ]
        else:
            a_stages = _gather_stage_tiles(a_mine, COL_AXIS, p)
            b_stages = _gather_stage_tiles(b_mine, ROW_AXIS, p)
            chunks = [
                esc_expand(sr, a_stages[s], CSR.from_tuples(b_stages[s]),
                           flop_capacity)
                for s in range(p)
            ]
        partial_c = SpTuples.concat(chunks)  # [lr × lcB] partial, uncompacted
        runs, piece_over = _fiber_exchange(
            partial_c, L, w_out, piece_capacity,
            sort_pieces=(merge == "runs"),
        )
        out, merge_over, hash_over = _fiber_merge(
            sr, runs, out_capacity, merge
        )
        overflow = jnp.stack([piece_over, merge_over, hash_over])
        overflow = lax.pmax(
            lax.pmax(lax.pmax(overflow, ROW_AXIS), COL_AXIS), LAYER_AXIS
        )
        return (
            out.rows[None, None, None], out.cols[None, None, None],
            out.vals[None, None, None], out.nnz[None, None, None],
            overflow[None, None, None],
        )

    r, c, v, n, overflow = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE3_SPEC,) * 8,
        out_specs=(TILE3_SPEC,) * 5,
        check_vma=False,
    )(A.rows, A.cols, A.vals, A.nnz, B.rows, B.cols, B.vals, B.nnz)
    mat = SpParMat3D(
        rows=r, cols=c, vals=v, nnz=n,
        nrows=A.nrows, ncols=B.ncols, split="col", grid=grid,
    )
    return mat, overflow[0, 0, 0]


@jax.jit
def summa3d_stage_flops(A: SpParMat3D, B: SpParMat3D) -> Array:
    """[p, L, pr, pc] float32 flops per stage per (layer, tile).

    The distributed symbolic pass of the 3D product — same scheme as the 2D
    ``summa_stage_flops`` (index arrays only cross the ICI), one gather per
    within-layer axis.
    """
    grid = A.grid
    p = grid.pr
    lrB = B.tile_rows
    lrA = A.tile_rows
    lcA = A.tile_cols

    def body(ar, ac, br):
        a_rows, a_cols = ar[0, 0, 0], ac[0, 0, 0]
        b_rows = br[0, 0, 0]
        ag_rows = lax.all_gather(a_rows, COL_AXIS)
        ag_cols = lax.all_gather(a_cols, COL_AXIS)
        bg_rows = lax.all_gather(b_rows, ROW_AXIS)
        per_stage = []
        for s in range(p):
            b_valid = bg_rows[s] < lrB
            blens = jax.ops.segment_sum(
                b_valid.astype(jnp.int32), bg_rows[s], num_segments=lrB + 1
            )
            # chunked-expansion slots, not raw flops (ops.spgemm.CHUNK_W)
            from ..ops.spgemm import CHUNK_W

            blens = -(-blens // CHUNK_W) * CHUNK_W
            a_valid = ag_rows[s] < lrA
            k = jnp.minimum(ag_cols[s], lrB)
            per_stage.append(
                jnp.sum(jnp.where(a_valid, blens[k], 0).astype(jnp.float32))
            )
        mine = jnp.stack(per_stage)  # [p]
        # replicated output: host-addressable under multi-host (see the 2D
        # summa_stage_flops note)
        g = lax.all_gather(
            lax.all_gather(lax.all_gather(mine, COL_AXIS), ROW_AXIS),
            LAYER_AXIS,
        )  # [L, pr, pc, p]
        return jnp.transpose(g, (3, 0, 1, 2))

    return jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE3_SPEC,) * 3,
        out_specs=P(),
        check_vma=False,
    )(A.rows, A.cols, B.rows)


# --- windowed 3D SUMMA (the round-9 tier: per-layer dense window
# accumulators on the 3-axis mesh, ParFriends.h:2919-3213 with the
# windowed local kernel in place of the hash SpGEMM) -------------------------


@partial(
    jax.jit, static_argnames=("block_rows", "block_cols", "chunk_w")
)
def summa3d_window_flops_pair(
    A3: SpParMat3D, B3: SpParMat3D, block_rows: int, block_cols: int,
    chunk_w: int = 1,
) -> Array:
    """[2, L, nblocks, ncolwin, p, pr, pc]: the 3D-resolved windowed
    symbolic pass — per-LAYER flop counts per (A row block, B col
    window) per stage per tile, same (chunk-padded, true) pair contract
    as the 2D ``summa_window_flops_pair`` (whose per-stage inner kernel
    it shares)."""
    from .spgemm import _window_stage_symbolic

    assert A3.split == "col" and B3.split == "row"
    assert A3.grid == B3.grid and A3.ncols == B3.nrows
    grid = A3.grid
    p = grid.pr
    assert grid.pr == grid.pc, "SUMMA3D requires square layer grids"
    lrA = A3.tile_rows
    lrB, lcB = B3.tile_rows, B3.tile_cols
    assert A3.tile_cols == lrB, "contraction blocking mismatch"
    nblocks = -(-lrA // block_rows)
    ncw = -(-lcB // block_cols)

    def body(ar, ac, br, bc):
        a_rows, a_cols = ar[0, 0, 0], ac[0, 0, 0]
        b_rows, b_cols = br[0, 0, 0], bc[0, 0, 0]
        ag_rows = lax.all_gather(a_rows, COL_AXIS)
        ag_cols = lax.all_gather(a_cols, COL_AXIS)
        bg_rows = lax.all_gather(b_rows, ROW_AXIS)
        bg_cols = lax.all_gather(b_cols, ROW_AXIS)
        per_stage = [
            _window_stage_symbolic(
                ag_rows[s], ag_cols[s], bg_rows[s], bg_cols[s],
                lrA, lrB, block_rows, block_cols, nblocks, ncw, chunk_w,
            )
            for s in range(p)
        ]
        mine = jnp.stack(per_stage)  # [p, 2, nblocks, ncw]
        g2 = lax.all_gather(
            lax.all_gather(lax.all_gather(mine, COL_AXIS), ROW_AXIS),
            LAYER_AXIS,
        )  # [L, pr, pc, p, 2, nblocks, ncw]
        # -> [2, L, nblocks, ncw, p, pr, pc]
        return jnp.transpose(g2, (4, 0, 5, 6, 3, 1, 2))

    return jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE3_SPEC,) * 4,
        out_specs=P(),
        check_vma=False,
    )(A3.rows, A3.cols, B3.rows, B3.cols)


def summa3d_window_flops_host(
    grid3: Grid3D, rows_a, cols_a, rows_b, cols_b,
    nrows_a: int, ncols_a: int, ncols_b: int,
    block_rows: int, block_cols: int, chunk_w: int = 0,
) -> np.ndarray:
    """Host-numpy twin of ``summa3d_window_flops_pair`` (one chunk_w at
    a time): [L, nblocks, ncolwin, p, pr, pc] float64 from global COO
    arrays, zero device interaction — the host-side 3D sizing path."""
    L = grid3.layers
    p = grid3.pr
    assert grid3.pr == grid3.pc, "SUMMA3D requires square layer grids"
    lrA = grid3.local_rows(nrows_a)
    lcA = grid3.local_cols(ncols_a)
    lrB = grid3.local_rows(ncols_a)
    lcB = grid3.local_cols(ncols_b)
    assert lcA == lrB, "A col-blocking must equal B row-blocking"
    assert lcA % L == 0 and lrB % L == 0, (lcA, lrB, L)
    tcA = lcA // L  # A's per-layer contraction slice == B's trB
    nb = -(-lrA // block_rows)
    ncw = -(-lcB // block_cols)
    rows_a = np.asarray(rows_a, np.int64)
    cols_a = np.asarray(cols_a, np.int64)
    rows_b = np.asarray(rows_b, np.int64)
    cols_b = np.asarray(cols_b, np.int64)
    ia, sa = rows_a // lrA, cols_a // lcA
    la, ka = (cols_a % lcA) // tcA, (cols_a % lcA) % tcA
    ga = (rows_a % lrA) // block_rows
    countA = np.bincount(
        ((((la * p + ia) * p + sa) * nb) + ga) * tcA + ka,
        minlength=L * p * p * nb * tcA,
    ).reshape(L, p, p, nb, tcA)
    sb, jb = rows_b // lrB, cols_b // lcB
    lb, kb = (rows_b % lrB) // tcA, (rows_b % lrB) % tcA
    hb = (cols_b % lcB) // block_cols
    countB = np.bincount(
        ((((lb * p + sb) * p + jb) * ncw) + hb) * tcA + kb,
        minlength=L * p * p * ncw * tcA,
    ).reshape(L, p, p, ncw, tcA)
    if chunk_w:
        countB = -(-countB // chunk_w) * chunk_w
    # flops[l, g, h, s, i, j] = sum_k countA[l,i,s,g,k]*countB[l,s,j,h,k]
    return np.einsum(
        "lisgk,lsjhk->lghsij",
        countA.astype(np.float64), countB.astype(np.float64),
    )


@partial(jax.jit, static_argnames=("block_cols",))
def summa3d_window_bnnz(B3: SpParMat3D, block_cols: int) -> Array:
    """[L, pr, pc, ncolwin] int32, replicated: per-layer B-tile nnz per
    col window — the 3D twin of ``summa_window_bnnz`` (the dot
    backend's static panel slice capacity)."""
    lrB, lcB = B3.tile_rows, B3.tile_cols
    ncw = -(-lcB // block_cols)

    def body(br, bc):
        b_rows, b_cols = br[0, 0, 0], bc[0, 0, 0]
        valid = b_rows < lrB
        h = jnp.where(valid, b_cols // block_cols, ncw).astype(jnp.int32)
        mine = jax.ops.segment_sum(
            valid.astype(jnp.int32), h, num_segments=ncw + 1
        )[:ncw]
        return lax.all_gather(
            lax.all_gather(lax.all_gather(mine, COL_AXIS), ROW_AXIS),
            LAYER_AXIS,
        )  # [L, pr, pc, ncw]

    return jax.shard_map(
        body,
        mesh=B3.grid.mesh,
        in_specs=(TILE3_SPEC,) * 2,
        out_specs=P(),
        check_vma=False,
    )(B3.rows, B3.cols)


def summa3d_window_bnnz_host(
    grid3: Grid3D, rows_b, cols_b, ncols_a: int, ncols_b: int,
    block_cols: int,
) -> np.ndarray:
    """Host twin of ``summa3d_window_bnnz``: [L, pr, pc, ncolwin]."""
    L = grid3.layers
    lrB = grid3.local_rows(ncols_a)
    lcB = grid3.local_cols(ncols_b)
    trB = lrB // L
    ncw = -(-lcB // block_cols)
    rows_b = np.asarray(rows_b, np.int64)
    cols_b = np.asarray(cols_b, np.int64)
    sb, jb = rows_b // lrB, cols_b // lcB
    lb = (rows_b % lrB) // trB
    hb = (cols_b % lcB) // block_cols
    return np.bincount(
        (((lb * grid3.pr + sb) * grid3.pc + jb) * ncw) + hb,
        minlength=L * grid3.pr * grid3.pc * ncw,
    ).reshape(L, grid3.pr, grid3.pc, ncw)


def windowed_plan3d(
    per_window_padded: np.ndarray | None,
    per_window_true: np.ndarray,
    block_rows: int,
    block_cols: int,
    tile_rows: int,
    tile_cols_b: int,
    slack: float = 1.02,
) -> tuple[tuple, tuple, tuple]:
    """3D twin of ``windowed_plan_2d`` over [L, nb, ncw, p, pr, pc]
    counts: ONE SPMD program runs on every layer, so each window's caps
    are the MAX over layers and a window is skipped only when EVERY
    layer's symbolic count is zero.  Folding the layer axis into the
    tile axes makes this exactly the 2D plan rule."""
    from .spgemm import windowed_plan_2d

    def fold(x):
        if x is None:
            return None
        x = np.asarray(x, np.float64)
        return np.moveaxis(x, 0, 3)  # [nb, ncw, p, L, pr, pc]

    return windowed_plan_2d(
        fold(per_window_padded), fold(per_window_true),
        block_rows, block_cols, tile_rows, tile_cols_b, slack=slack,
    )


@partial(
    jax.jit,
    static_argnames=(
        "sr", "block_rows", "flop_caps", "out_caps", "skip", "backend",
        "mode", "chunk_w", "interpret", "block_cols", "panel_cap",
        "piece_capacity", "out_capacity", "ring", "pipeline", "merge",
    ),
)
def summa3d_spgemm_windowed(
    sr: Semiring,
    A3: SpParMat3D,
    B3: SpParMat3D,
    *,
    block_rows: int,
    flop_caps: tuple,
    out_caps: tuple,
    skip: tuple,
    backend: str = "scatter",
    mode: str = "f32",
    chunk_w: int = 8,
    interpret: bool = False,
    block_cols: int | None = None,
    panel_cap: int | None = None,
    piece_capacity: int,
    out_capacity: int,
    ring: bool = False,
    pipeline: bool = True,
    merge: str = "sort",
) -> tuple[SpParMat3D, Array]:
    """C (col-split) = A (col-split) ⊗ B (row-split): the WINDOWED 3D
    SUMMA — ``Mult_AnXBn_SUMMA3D`` with the sort-free windowed local
    kernel in place of the per-stage ESC expand.

    Each layer runs the per-device windowed accumulate+extract core of
    the 2D tier — ``spgemm._windowed_gathered_compute`` (default), or
    with ``ring=True`` the STAGE-PIPELINED CAROUSEL
    (``spgemm._windowed_carousel_compute``): operands rotate
    neighbor-to-neighbor in two-slot buffers on the within-layer joint
    (row, col) axis, O(2·tile) sparse operand memory instead of
    O(p·tile), and with ``pipeline=True`` stage s+1's ppermute issued
    before stage s's accumulate (``pipeline=False`` pins the
    rotate→compute→rotate serial chain via optimization_barrier — the
    A/B measurement control).  Both backends, duplicate-safe
    ``densify_combine``, packed launch list, per-window symbolic caps
    sized by ``windowed_plan3d`` over the layer slices, identical chunk
    layouts across schedules.  Each layer produces one sparse
    [tile_rows × tile_cols] partial; the L partials ride the fiber
    ``all_to_all`` (``_fiber_exchange``) and the ``merge``-selected
    combine tier (``_fiber_merge``).  With the scatter / 1D-dot
    backends the partial is already globally (row, col)-sorted
    (ascending row blocks of sorted extractions), so ``merge="runs"``
    eliminates the fiber reduce's sort ENTIRELY; the dot2d chunk order
    is window-major within a block, so its pieces pre-sort on the
    exchange side.  The payoff mirrors the reference's 3DSpGEMM:
    per-layer stage operands carry 1/L of the contraction, so
    per-stage gather volume shrinks L-fold where the 2D carousel
    saturates.

    Returns ``(C, overflow[4])``: per-device max of (extraction
    overflow, fiber piece drop, merge distinct-keys − out_capacity,
    hash placement overflow) — all ≤ 0 means exact (with symbolic caps
    the first two are structurally ≤ 0); a positive hash overflow
    means the CALLER must rerun through a sorted tier
    (``spgemm3d_windowed`` does this automatically).
    """
    from .spgemm import (
        _PALLAS_KINDS,
        _gather_stage_tiles,
        _windowed_carousel_compute,
        _windowed_gathered_compute,
    )
    from ..ops.spgemm import scatter_combine_for

    assert A3.split == "col" and B3.split == "row"
    assert A3.grid == B3.grid and A3.ncols == B3.nrows
    assert merge in MERGE_TIERS, merge
    grid = A3.grid
    p = grid.pr
    assert grid.pr == grid.pc, "SUMMA3D requires square layer grids"
    L = grid.layers
    lr = A3.tile_rows  # full local rows of C
    lrB, lcB = B3.tile_rows, B3.tile_cols
    assert A3.tile_cols == lrB, "contraction blocking mismatch"
    assert lcB % L == 0
    w_out = lcB // L
    two_d = backend == "dot" and block_cols is not None
    if backend == "dot":
        assert sr.name in _PALLAS_KINDS, sr.name
        if two_d:
            assert panel_cap is not None and panel_cap >= 1
    else:
        assert backend == "scatter", backend
    assert scatter_combine_for(sr) is not None, sr.name
    if obs.ENABLED:
        obs.count(
            "trace.summa3d_spgemm_windowed",
            backend=("dot2d" if two_d else backend),
            ring=ring, merge=merge,
        )
        if ring and pipeline and p > 1:
            # trace-time: per-layer carousel stages whose successor
            # rotation is issued early in this compiled program
            obs.count("spgemm.pipeline.stages_overlapped", p - 1)
    zero = float(np.asarray(sr.zero_fn(A3.vals.dtype)))
    static = dict(
        lrA=lr, lrB=lrB, lcB=lcB, block_rows=block_rows,
        flop_caps=flop_caps, out_caps=out_caps, skip=skip,
        backend=backend, mode=mode, chunk_w=chunk_w,
        interpret=interpret, block_cols=block_cols if two_d else None,
        panel_cap=panel_cap, zero=zero, dtype=A3.vals.dtype,
    )
    # scatter / 1D-dot chunk layout: ascending row blocks, each chunk
    # row-major-sorted by the windowed extraction → the concatenated
    # partial's valid entries are globally (row, col)-sorted and the
    # column-range piece selection preserves that; dot2d chunks are
    # window-major within a block and need the exchange-side pre-sort
    partial_sorted = not two_d

    def body(ar, ac, av, an, br, bc, bv, bn):
        a_mine = A3.local_tile(ar, ac, av, an)
        b_mine = B3.local_tile(br, bc, bv, bn)
        if ring:
            chunks, worst = _windowed_carousel_compute(
                sr, a_mine, b_mine, p=p, pipeline=pipeline, **static
            )
        else:
            a_stages = _gather_stage_tiles(a_mine, COL_AXIS, p)
            b_stages = _gather_stage_tiles(b_mine, ROW_AXIS, p)
            chunks, worst = _windowed_gathered_compute(
                sr, a_stages, b_stages, **static
            )
        if not chunks:  # every window skipped on this layer
            chunks.append(SpTuples.empty(lr, lcB, 1, A3.vals.dtype))
        partial_c = SpTuples.concat(chunks)
        runs, piece_over = _fiber_exchange(
            partial_c, L, w_out, piece_capacity,
            sort_pieces=(merge == "runs" and not partial_sorted),
        )
        out, merge_over, hash_over = _fiber_merge(
            sr, runs, out_capacity, merge
        )
        overflow = jnp.stack([worst, piece_over, merge_over, hash_over])
        overflow = lax.pmax(
            lax.pmax(lax.pmax(overflow, ROW_AXIS), COL_AXIS), LAYER_AXIS
        )
        return (
            out.rows[None, None, None], out.cols[None, None, None],
            out.vals[None, None, None], out.nnz[None, None, None],
            overflow[None, None, None],
        )

    r, c, v, n, overflow = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE3_SPEC,) * 8,
        out_specs=(TILE3_SPEC,) * 5,
        check_vma=False,
    )(A3.rows, A3.cols, A3.vals, A3.nnz, B3.rows, B3.cols, B3.vals, B3.nnz)
    mat = SpParMat3D(
        rows=r, cols=c, vals=v, nnz=n,
        nrows=A3.nrows, ncols=B3.ncols, split="col", grid=grid,
    )
    return mat, overflow[0, 0, 0]


def summa3d_compatible(grid3: Grid3D, nrows_a: int, ncols_a: int,
                       ncols_b: int) -> bool:
    """True iff (A: nrows_a × ncols_a) ⊗ (B: ncols_a × ncols_b) can be
    laid out on ``grid3`` (square layer grid; the col-split of A, the
    row-split of B, and C's fiber pieces all divide evenly over the
    layers) — the router's gate before choosing the 3D path."""
    L = grid3.layers
    if grid3.pr != grid3.pc:
        return False
    lcA = grid3.local_cols(ncols_a)
    lrB = grid3.local_rows(ncols_a)
    lcB = grid3.local_cols(ncols_b)
    return (
        lcA == lrB
        and lcA % L == 0
        and lrB % L == 0
        and lcB % L == 0
    )


def spgemm3d_windowed(
    sr: Semiring,
    A3: SpParMat3D,
    B3: SpParMat3D,
    *,
    block_rows: int | None = None,
    block_cols: int | None = None,
    backend: str | None = None,
    mode: str = "f32",
    slack: float = 1.02,
    interpret: bool = False,
    merge: str | None = None,
    ring: bool = False,
    pipeline: bool = True,
) -> SpParMat3D:
    """Sized entry for the windowed 3D tier: 3D symbolic pass →
    ``windowed_plan3d`` (caps maxed over layers) → the compiled
    ``summa3d_spgemm_windowed``.  Both accumulate backends.

    ``merge`` picks the fiber-reduce combine tier (``MERGE_TIERS``;
    ``None`` is the L/collision heuristic, ``_merge_heuristic``).
    ``ring``/``pipeline`` pick the per-layer SUMMA schedule (the r9
    carousel).  A hash-tier placement overflow is COUNTED
    (``spgemm.merge.hash_overflow``) and the product transparently
    reruns through the sorted-runs tier — never wrong, only slower.
    A fiber piece overflow raises a diagnostic naming the ``slack``
    knob instead of truncating downstream."""
    from .spgemm import (
        WINDOWED_CHUNK_W,
        default_block_cols,
        default_block_rows,
        host_value,
        packed_windows,
        packed_windows_2d,
        panel_cap_from_bnnz,
        resolve_spgemm_backend,
    )

    backend = resolve_spgemm_backend(backend)
    grid = A3.grid
    L = grid.layers
    lr = A3.tile_rows
    lrB, lcB = B3.tile_rows, B3.tile_cols
    chunk_w = WINDOWED_CHUNK_W
    if block_rows is None:
        block_rows = default_block_rows(lr, lcB)
    if backend == "dot":
        if block_cols is None:
            block_cols = default_block_cols(lrB, lcB)
        pair = host_value(
            summa3d_window_flops_pair(A3, B3, block_rows, block_cols,
                                      chunk_w=1)
        )
        flop_caps, out_caps, skip = windowed_plan3d(
            None, pair[1], block_rows, block_cols, lr, lcB, slack=slack
        )
        panel_cap = panel_cap_from_bnnz(
            host_value(summa3d_window_bnnz(B3, block_cols)),
            int(B3.capacity),
        )
        npk = len(packed_windows_2d(skip))
        ntot = sum(len(row) for row in skip)
        per_block_bound = [sum(row) for row in out_caps]
        pieces_sorted = False  # dot2d chunk order is window-major
    else:
        # scatter: the window pass with ONE full-width window gives the
        # per-block (padded, true) pair in one kernel
        pair = host_value(
            summa3d_window_flops_pair(A3, B3, block_rows, lcB,
                                      chunk_w=chunk_w)
        )
        fc2, oc2, sk2 = windowed_plan3d(
            pair[0], pair[1], block_rows, lcB, lr, lcB, slack=slack
        )
        flop_caps = tuple(row[0] for row in fc2)
        out_caps = tuple(row[0] for row in oc2)
        skip = tuple(row[0] for row in sk2)
        block_cols = panel_cap = None
        npk = len(packed_windows(skip))
        ntot = len(skip)
        per_block_bound = list(out_caps)
        pieces_sorted = True
    # fiber piece / merge capacities from the same symbolic bounds: one
    # outgoing piece can hold at most the tile's whole extracted
    # partial; the merge receives L pieces and compacts to at most the
    # dense piece
    rnd = lambda x: 1 << (max(int(x), 1) - 1).bit_length()
    piece_cap = rnd(min(sum(per_block_bound), lr * lcB))
    out_cap = min(rnd(piece_cap * L), max(lr * (lcB // L), 1))
    merge_source = "heuristic" if merge is None else "arg"
    if merge is None:
        # collision estimate: total merge-input slots over the
        # distinct-key bound — ≈ how many partial entries fold into
        # each output key across the fiber
        merge = _merge_heuristic(
            sr, L, piece_cap * L / max(out_cap, 1), pieces_sorted
        )
    assert merge in MERGE_TIERS, merge
    if obs.ENABLED:
        obs.gauge("spgemm.summa3d.layers", L)
        obs.count("spgemm.windowed.windows_packed", npk)
        obs.gauge(
            "spgemm.windowed.pack_ratio", npk / ntot if ntot else 0.0
        )
        obs.count(
            "spgemm.merge.tier", tier=merge, source=merge_source,
            op="spgemm3d",
        )

    def run_kernel(mg):
        C, overflow = summa3d_spgemm_windowed(
            sr, A3, B3, block_rows=block_rows, flop_caps=flop_caps,
            out_caps=out_caps, skip=skip, backend=backend, mode=mode,
            chunk_w=chunk_w, interpret=interpret, block_cols=block_cols,
            panel_cap=panel_cap, piece_capacity=piece_cap,
            out_capacity=out_cap, ring=ring, pipeline=pipeline,
            merge=mg,
        )
        over = tuple(int(x) for x in np.asarray(host_value(overflow)))
        _check_fiber_overflow(over[1], piece_cap, "spgemm3d_windowed",
                              slack)
        return (C,) + over

    C, extract_over, _, merge_over, hash_over = run_kernel(merge)
    if hash_over > 0:
        # counted fallback: the hash table failed to place hash_over
        # entries — rerun the ALREADY-SIZED kernel through the
        # sorted-runs tier (never wrong, only slower); the counter is
        # how operators notice a mis-sized table
        if obs.ENABLED:
            obs.count("spgemm.merge.hash_overflow", hash_over)
            obs.count(
                "spgemm.merge.tier", tier="runs",
                source="hash_fallback", op="spgemm3d",
            )
        C, extract_over, _, merge_over, _ = run_kernel("runs")
    assert extract_over <= 0 and merge_over <= 0, (
        f"windowed 3D tier overflowed its symbolic bound "
        f"(extraction {extract_over}, merge {merge_over})"
    )
    return C


def _check_fiber_overflow(piece_over: int, piece_cap: int, who: str,
                          slack: float) -> None:
    """Shared fiber piece-overflow diagnostic: the exchange DETECTED
    dropped entries (round-13 satellite — before this the count was
    returned and silently ignored by some callers, truncating the
    product downstream).  Counted as ``spgemm.summa3d.piece_overflow``
    and raised with the knob that fixes it."""
    if piece_over <= 0:
        return
    if obs.ENABLED:
        obs.count("spgemm.summa3d.piece_overflow", piece_over)
    raise ValueError(
        f"{who}: fiber exchange overflowed — a piece exceeded its "
        f"piece_capacity={piece_cap} by {piece_over} entries and the "
        f"all_to_all would have dropped them; raise the sizing slack "
        f"(slack={slack} at this call; spgemm3d(..., slack=) / "
        f"{who}(..., slack=)) or pass a larger explicit piece capacity"
    )


def spgemm3d(
    sr: Semiring, A: SpParMat3D, B: SpParMat3D, slack: float = 1.05,
    *, tier: str | None = None, backend: str | None = None,
    mode: str = "f32", block_rows: int | None = None,
    block_cols: int | None = None, interpret: bool = False,
    merge: str | None = None, ring: bool = False,
    pipeline: bool = True,
) -> SpParMat3D:
    """Unjitted entry: distributed symbolic sizing → compiled 3D SUMMA.

    ``tier`` picks the per-layer local kernel: ``"esc"`` (``None``'s
    default — the classic expand/sort/compress stage kernel, exact for
    every semiring) or ``"windowed"`` (the sort-free dense-window tier,
    ``spgemm3d_windowed``).  Nothing else decides: no environment
    variable, no file, no timed guess.  The ESC sizing pass mirrors
    ``EstPerProcessNnzSUMMA``'s role (ParFriends.h:1243); capacities
    round to powers of two (clamped to the dense-tile bound) for
    compile-cache reuse.

    ``merge`` picks the fiber-reduce combine tier (``MERGE_TIERS``:
    sort | runs | hash); ``None`` is the heuristic on L and the
    collision estimate (``_merge_heuristic``).  ``ring``/``pipeline``:
    the per-layer SUMMA's carousel schedule.
    """
    from .. import obs
    from ..ops.spgemm import scatter_combine_for

    if tier is None:
        tier = "esc"
    assert tier in ("esc", "windowed"), tier
    if tier == "windowed":
        return spgemm3d_windowed(
            sr, A, B, block_rows=block_rows, block_cols=block_cols,
            backend=backend, mode=mode, slack=max(slack - 0.03, 1.02),
            interpret=interpret, merge=merge, ring=ring,
            pipeline=pipeline,
        )
    if ring and not pipeline:
        # the ESC ring rides _carousel_stages, which is ALWAYS
        # pipelined (PR 7 dropped its dead pipeline param: trace order
        # alone is no serial control) — reject rather than mislabel a
        # pipelined run as the serial A/B control (the windowed tier
        # carries the real optimization_barrier control)
        raise ValueError(
            "spgemm3d: the esc tier's carousel has no serial "
            "(pipeline=False) control — use tier='windowed' for the "
            "pipelined-vs-serial A/B"
        )
    grid = A.grid
    L = grid.layers
    from .spgemm import host_value
    per_stage = host_value(summa3d_stage_flops(A, B)).astype(np.float64)
    flop_cap = max(int(per_stage.max() * slack) + 1, 1)
    total = per_stage.sum(axis=0)  # per (layer, tile)
    piece_cap = max(int(total.max() * slack) + 1, 1)
    dense_tile = A.tile_rows * (B.tile_cols // L)
    out_cap = max(min(int(total.max() * L * slack) + 1, dense_tile), 1)
    rnd = lambda x: 1 << (x - 1).bit_length()
    piece_cap = rnd(piece_cap)
    out_cap = min(rnd(out_cap), max(dense_tile, 1))
    merge_source = "heuristic" if merge is None else "arg"
    if merge is None:
        # ESC stage chunks are UNSORTED (pieces_sorted=False): "runs"
        # would pay L piece-local pre-sorts, so the heuristic keeps the
        # one concat sort at low L and switches to hash only where the
        # collision estimate says the O(nnz) table amortizes
        merge = _merge_heuristic(
            sr, L, piece_cap * L / max(out_cap, 1), pieces_sorted=False
        )
    if merge == "hash" and scatter_combine_for(sr) is None:
        # a forced hash on a generic monoid must DEGRADE here, not
        # assert mid-trace inside the shard_map body — the 2D spgemm
        # entry's convention; runs is exact for every semiring
        merge = "runs"
        merge_source = f"{merge_source}_degraded"
    assert merge in MERGE_TIERS, merge
    if obs.ENABLED:
        obs.count(
            "spgemm.merge.tier", tier=merge, source=merge_source,
            op="spgemm3d",
        )
    def run_kernel(mg):
        return summa3d_spgemm(
            sr, A, B,
            flop_capacity=rnd(flop_cap),
            out_capacity=out_cap,
            piece_capacity=piece_cap,
            ring=ring, merge=mg,
        )

    C, overflow = run_kernel(merge)
    piece_over, merge_over, hash_over = (
        int(x) for x in np.asarray(host_value(overflow))
    )
    _check_fiber_overflow(piece_over, piece_cap, "spgemm3d", slack)
    if hash_over > 0:
        # counted fallback: rerun the ALREADY-SIZED kernel through the
        # sorted-runs tier (no re-entry into the routing entry)
        if obs.ENABLED:
            obs.count("spgemm.merge.hash_overflow", hash_over)
            obs.count(
                "spgemm.merge.tier", tier="runs",
                source="hash_fallback", op="spgemm3d",
            )
        C, overflow = run_kernel("runs")
        piece_over, merge_over, _ = (
            int(x) for x in np.asarray(host_value(overflow))
        )
        _check_fiber_overflow(piece_over, piece_cap, "spgemm3d", slack)
    assert merge_over <= 0, (
        f"spgemm3d: merge distinct keys exceeded out_capacity by "
        f"{merge_over}; raise slack"
    )
    return C


# --- 2D <-> 3D conversions (≈ SpParMat3D(SpParMat&) / layermat readback,
# SpParMat3D.cpp:74-145, 197-320) ------------------------------------------


def _globalize2d(A):
    """2D tile arrays → global-id arrays [pr, pc, cap] (no communication:
    adds tile offsets on the sharded arrays in place; padding → nrows/ncols
    sentinels)."""
    from .spmat import SpParMat  # noqa: F401 (type context)

    g = A.grid
    lr, lc = A.local_rows, A.local_cols
    valid = A.rows < lr
    ioff = jnp.arange(g.pr, dtype=jnp.int32)[:, None, None]
    joff = jnp.arange(g.pc, dtype=jnp.int32)[None, :, None]
    gr = jnp.where(valid, A.rows + ioff * lr, A.nrows)
    gc = jnp.where(valid, A.cols + joff * lc, A.ncols)
    return gr.astype(jnp.int32), gc.astype(jnp.int32), A.vals


def _globalize3d(A3: SpParMat3D):
    """3D tile arrays → global-id arrays [L, pr, pc, cap] (split-aware)."""
    g = A3.grid
    L = g.layers
    lr, lc = g.local_rows(A3.nrows), g.local_cols(A3.ncols)
    tr, tc = A3.tile_rows, A3.tile_cols
    valid = A3.rows < tr
    loff = jnp.arange(L, dtype=jnp.int32)[:, None, None, None]
    ioff = jnp.arange(g.pr, dtype=jnp.int32)[None, :, None, None]
    joff = jnp.arange(g.pc, dtype=jnp.int32)[None, None, :, None]
    if A3.split == "col":
        gr = A3.rows + ioff * lr
        gc = A3.cols + joff * lc + loff * tc
    else:
        gr = A3.rows + ioff * lr + loff * tr
        gc = A3.cols + joff * lc
    gr = jnp.where(valid, gr, A3.nrows)
    gc = jnp.where(valid, gc, A3.ncols)
    return gr.astype(jnp.int32), gc.astype(jnp.int32), A3.vals


@partial(
    jax.jit,
    static_argnames=("grid", "nrows", "ncols", "split", "stage_capacity",
                     "tile_capacity"),
)
def redistribute_coo3d(
    grid: Grid3D,
    rows: Array,
    cols: Array,
    vals: Array,
    nrows: int,
    ncols: int,
    *,
    split: str,
    stage_capacity: int,
    tile_capacity: int,
):
    """Route device-resident GLOBAL tuples to their 3D owner tiles.

    rows/cols/vals: [L, pr, pc, chunk] arbitrary global tuples per device
    (invalid slots: row >= nrows). Three fixed-capacity all_to_all hops —
    by owner column over "c", owner row over "r", owner layer over "l" —
    the dimension-ordered extension of ``redistribute_coo``'s 2D routing
    (the fiber Alltoallv of the reference's 2D→3D conversion,
    SpParMat3D.cpp:74-145). Returns (SpParMat3D, dropped count).
    """
    from .redistribute import _bucket_route

    L = grid.layers
    lr, lc = grid.local_rows(nrows), grid.local_cols(ncols)
    split_dim = lc if split == "col" else lr
    if split_dim % L:
        raise ValueError(
            f"3D {split}-split needs the local {'column' if split == 'col' else 'row'} "
            f"count ({split_dim}) to divide evenly over {L} layers; pad the "
            f"matrix dims or choose a different grid"
        )
    w = split_dim // L
    tr = lr if split == "col" else w
    tc = w if split == "col" else lc
    pr_, pc_ = grid.pr, grid.pc

    def hop(r, c, v, dest, ndest, axis):
        br, bc, bv, drop = _bucket_route(
            dest.astype(jnp.int32), r, c, v, ndest, stage_capacity,
            jnp.int32(nrows), jnp.int32(ncols),
        )
        br = lax.all_to_all(br, axis, split_axis=0, concat_axis=0)
        bc = lax.all_to_all(bc, axis, split_axis=0, concat_axis=0)
        bv = lax.all_to_all(bv, axis, split_axis=0, concat_axis=0)
        return br.reshape(-1), bc.reshape(-1), bv.reshape(-1), drop

    def body(r, c, v):
        r0, c0, v0 = r[0, 0, 0], c[0, 0, 0], v[0, 0, 0]
        valid = r0 < nrows
        oj = jnp.where(valid, c0 // lc, pc_)
        r1, c1, v1, d1 = hop(r0, c0, v0, oj, pc_, COL_AXIS)
        valid = r1 < nrows
        oi = jnp.where(valid, r1 // lr, pr_)
        r2, c2, v2, d2 = hop(r1, c1, v1, oi, pr_, ROW_AXIS)
        valid = r2 < nrows
        if split == "col":
            ol = jnp.where(valid, (c2 % lc) // w, L)
        else:
            ol = jnp.where(valid, (r2 % lr) // w, L)
        r3, c3, v3, d3 = hop(r2, c2, v2, ol, L, LAYER_AXIS)
        # localize
        i = lax.axis_index(ROW_AXIS)
        j = lax.axis_index(COL_AXIS)
        ok = r3 < nrows
        if split == "col":
            lrow = jnp.where(ok, r3 - i * lr, tr)
            lcol = jnp.where(ok, (c3 - j * lc) % w, tc)
        else:
            lrow = jnp.where(ok, (r3 - i * lr) % w, tr)
            lcol = jnp.where(ok, c3 - j * lc, tc)
        nvalid = jnp.sum(ok).astype(jnp.int32)
        drop4 = jnp.maximum(nvalid - tile_capacity, 0)
        t = SpTuples(
            rows=lrow.astype(jnp.int32), cols=lcol.astype(jnp.int32),
            vals=jnp.where(ok, v3, 0), nnz=nvalid, nrows=tr, ncols=tc,
        )._select(ok).with_capacity(tile_capacity)
        dropped = lax.psum(
            lax.psum(lax.psum(d1 + d2 + d3 + drop4, ROW_AXIS), COL_AXIS),
            LAYER_AXIS,
        )
        return (
            t.rows[None, None, None], t.cols[None, None, None],
            t.vals[None, None, None], t.nnz[None, None, None],
            dropped[None],
        )

    r, c, v, n, dropped = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE3_SPEC,) * 3,
        # drop count replicated (multi-process-readable), see 2D twin
        out_specs=(TILE3_SPEC,) * 4 + (P(),),
        check_vma=False,
    )(rows, cols, vals)
    mat = SpParMat3D(
        rows=r, cols=c, vals=v, nnz=n, nrows=int(nrows), ncols=int(ncols),
        split=split, grid=grid,
    )
    return mat, dropped[0]


def _route_with_retry(route, chunk_cap: int, dest_fanouts, total: int,
                      ndev: int, slack: float, max_retries: int, what: str):
    """Shared conversion driver: size stage/tile capacities from the chunk
    shape and total nnz, route, and double capacities on dropped tuples."""
    per_dest = max(-(-chunk_cap // f) for f in dest_fanouts)
    stage_cap = 1 << max(int(np.ceil(np.log2(max(per_dest * slack, 1)))), 0)
    tile_cap = 1 << max(
        int(np.ceil(np.log2(max(total / ndev * slack, 1)))), 0
    )
    from .spgemm import host_value

    nd = 0
    for _ in range(max_retries + 1):
        mat, dropped = route(stage_cap, tile_cap)
        nd = int(host_value(dropped))
        if nd == 0:
            return mat
        stage_cap *= 2
        tile_cap *= 2
    raise ValueError(
        f"{what} dropped {nd} tuples after {max_retries} capacity doublings"
    )


def _rechunk(arr, ndev: int, sentinel):
    """Flatten tuple chunks and re-split over ``ndev`` devices, padding the
    tail with ``sentinel`` (an invalid row id — dropped by routing). Lets
    conversions change device count (a 2D square grid is never layers*p^2)."""
    flat = arr.reshape(-1)
    chunk = -(-flat.shape[0] // ndev)
    pad = ndev * chunk - flat.shape[0]
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), sentinel, flat.dtype)]
        )
    return flat, chunk


def spmat3d_from_spmat(
    A, grid3: Grid3D, split: str = "col", *, slack: float = 2.0,
    max_retries: int = 3,
) -> SpParMat3D:
    """2D → 3D conversion (≈ ``SpParMat3D(SpParMat&)``,
    SpParMat3D.cpp:74-145), fully on device.

    Globalizes the 2D tiles in place (no comm), reshards the tuple chunks
    onto the 3D mesh (XLA moves bytes over ICI at the jit boundary), then
    routes with ``redistribute_coo3d``. The source 2D grid may have ANY
    shape and device count (routing is by global id — no nested
    process-grid restriction), but the 3D grid's local split dimension must
    divide evenly over the layers (ValueError otherwise).
    """
    ndev3 = grid3.layers * grid3.pr * grid3.pc
    gr, gc, gv = _globalize2d(A)
    grf, cap = _rechunk(gr, ndev3, jnp.int32(A.nrows))
    gcf, _ = _rechunk(gc, ndev3, jnp.int32(A.ncols))
    gvf, _ = _rechunk(gv, ndev3, jnp.zeros((), gv.dtype))
    sh3 = grid3.tile_sharding()
    shape3 = (grid3.layers, grid3.pr, grid3.pc, cap)
    gr3 = jax.device_put(grf.reshape(shape3), sh3)
    gc3 = jax.device_put(gcf.reshape(shape3), sh3)
    gv3 = jax.device_put(gvf.reshape(shape3), sh3)
    total = int(np.asarray(jnp.sum(A.nnz)))

    def route(stage_cap, tile_cap):
        return redistribute_coo3d(
            grid3, gr3, gc3, gv3, A.nrows, A.ncols, split=split,
            stage_capacity=stage_cap, tile_capacity=tile_cap,
        )

    return _route_with_retry(
        route, cap, (grid3.pc, grid3.pr, grid3.layers), total, ndev3,
        slack, max_retries, "2D→3D conversion",
    )


def spmat_from_spmat3d(
    A3: SpParMat3D, grid2, *, slack: float = 2.0, max_retries: int = 3,
):
    """3D → 2D conversion (the layermat readback direction,
    SpParMat3D.cpp:197-320), fully on device: globalize, reshard chunks to
    the 2D mesh, route with the 2D ``redistribute_coo``."""
    from .redistribute import redistribute_coo

    gr, gc, gv = _globalize3d(A3)
    grf, cap = _rechunk(gr, grid2.size, jnp.int32(A3.nrows))
    gcf, _ = _rechunk(gc, grid2.size, jnp.int32(A3.ncols))
    gvf, _ = _rechunk(gv, grid2.size, jnp.zeros((), gv.dtype))
    sh2 = grid2.tile_sharding()
    shape2 = (grid2.pr, grid2.pc, cap)
    gr2 = jax.device_put(grf.reshape(shape2), sh2)
    gc2 = jax.device_put(gcf.reshape(shape2), sh2)
    gv2 = jax.device_put(gvf.reshape(shape2), sh2)
    total = int(np.asarray(jnp.sum(A3.nnz)))

    def route(stage_cap, tile_cap):
        return redistribute_coo(
            grid2, gr2, gc2, gv2, A3.nrows, A3.ncols,
            stage_capacity=stage_cap, tile_capacity=tile_cap,
        )

    return _route_with_retry(
        route, cap, (grid2.pc, grid2.pr), total, grid2.size,
        slack, max_retries, "3D→2D conversion",
    )


# --- 3D column operations (the MCL support ops on SpParMat3D) --------------
#
# A col-split SpParMat3D partitions global columns over (layer, grid-col):
# every global column lives wholly within one (l, j) tile column, spread
# over the pr row tiles. Column reductions are therefore the SAME kernels
# as 2D (segment-reduce per tile + psum over "r") run on the 3-axis mesh —
# the "r" collective acts within each layer automatically because axis
# names ARE the subcommunicators. This gives MemEfficientSpGEMM3D's prune
# hook real MCL semantics (≈ the column ops MCLPruneRecoverySelect needs,
# ParFriends.h:186-350, applied per layer as the reference does on its
# per-layer layermats).

COLVEC3_SPEC = P(LAYER_AXIS, COL_AXIS)


def _check_colsplit(A3: SpParMat3D):
    assert A3.split == "col", (
        "3D column ops operate on col-split matrices (columns partitioned "
        "over layer x grid-col); resplit row-split matrices first"
    )


@partial(jax.jit, static_argnames=("sr", "map_fn"))
def reduce3d_cols(sr: Semiring, A3: SpParMat3D, map_fn=None) -> Array:
    """Per-column fold over rows → [L, pc, tile_cols] (replicated over "r").

    The Reduce(Column) of the 3D matrix (≈ SpParMat::Reduce on each
    layermat)."""
    from ..ops.segment import segment_reduce

    _check_colsplit(A3)
    tc = A3.tile_cols

    def body(rows, cols, vals, nnz):
        t = A3.local_tile(rows, cols, vals, nnz)
        v = map_fn(t.vals) if map_fn is not None else t.vals
        local = segment_reduce(sr, v, t.cols, tc)
        from .collectives import axis_reduce

        return axis_reduce(sr, local, ROW_AXIS)[None, None]

    return jax.shard_map(
        body,
        mesh=A3.grid.mesh,
        in_specs=(TILE3_SPEC,) * 4,
        out_specs=COLVEC3_SPEC,
        check_vma=False,
    )(A3.rows, A3.cols, A3.vals, A3.nnz)


@jax.jit
def nnz_per_column3d(A3: SpParMat3D) -> Array:
    """[L, pc, tile_cols] int32 per-column nonzero counts."""
    _check_colsplit(A3)
    tc = A3.tile_cols

    def body(rows, cols, vals, nnz):
        t = A3.local_tile(rows, cols, vals, nnz)
        ids = jnp.where(t.valid_mask(), t.cols, tc)
        local = (
            jnp.zeros((tc,), jnp.int32).at[ids].add(1, mode="drop")
        )
        return lax.psum(local, ROW_AXIS)[None, None]

    return jax.shard_map(
        body,
        mesh=A3.grid.mesh,
        in_specs=(TILE3_SPEC,) * 4,
        out_specs=COLVEC3_SPEC,
        check_vma=False,
    )(A3.rows, A3.cols, A3.vals, A3.nnz)


@partial(jax.jit, static_argnames=("k",))
def kselect3d(A3: SpParMat3D, k: int, kvec: Array | None = None) -> Array:
    """Per-column k-th largest value → [L, pc, tile_cols].

    The Kselect1 of the 3D matrix (≈ SpParMat::Kselect1,
    SpParMat.cpp:1120-1742), via the same radix-select over
    order-preserving u32 keys as the 2D path. Columns with fewer than k
    entries return the dtype's minimum (keep-everything threshold).
    ``kvec``: optional [L, pc, tile_cols] per-column k override.
    """
    from .spmat import _key_bits, _monotone_key_u32, _u32_key_to_val
    from ..semiring import _minval

    _check_colsplit(A3)
    tc = A3.tile_cols
    dtype = A3.vals.dtype

    def body(rows, cols, vals, nnz, *maybe_k):
        t = A3.local_tile(rows, cols, vals, nnz)
        keys = _monotone_key_u32(t.vals)
        valid = t.valid_mask()
        ids = jnp.where(valid, t.cols, tc)
        idx = jnp.minimum(ids, tc - 1)
        kcol = (
            maybe_k[0][0, 0].astype(jnp.int32)
            if maybe_k
            else jnp.full((tc,), k, jnp.int32)
        )

        def col_count(ge_mask):
            local = jax.ops.segment_sum(
                ge_mask.astype(jnp.int32), ids, num_segments=tc
            )
            return lax.psum(local, ROW_AXIS)

        total = col_count(valid)
        kt = keys.dtype
        thresh = jnp.zeros((tc,), kt)
        for b in range(_key_bits(dtype) - 1, -1, -1):
            cand = thresh | jnp.asarray(1 << b, kt)
            cnt = col_count(valid & (keys >= cand[idx]))
            thresh = jnp.where(cnt >= kcol, cand, thresh)
        out = _u32_key_to_val(thresh, dtype)
        out = jnp.where(total < kcol, _minval(dtype), out)
        return out[None, None]

    args = (A3.rows, A3.cols, A3.vals, A3.nnz) + (
        (kvec,) if kvec is not None else ()
    )
    vspecs = (COLVEC3_SPEC,) if kvec is not None else ()
    return jax.shard_map(
        body,
        mesh=A3.grid.mesh,
        in_specs=(TILE3_SPEC,) * 4 + vspecs,
        out_specs=COLVEC3_SPEC,
        check_vma=False,
    )(*args)


@partial(jax.jit, static_argnames=("keep",))
def prune_column3d(A3: SpParMat3D, colvec: Array, keep) -> SpParMat3D:
    """Keep entry (i, j) iff ``keep(val, colvec[j])``
    (≈ SpParMat::PruneColumn, SpParMat.cpp:2567-2779)."""
    _check_colsplit(A3)

    def body(rows, cols, vals, nnz, vblk):
        t = A3.local_tile(rows, cols, vals, nnz)
        v = vblk[0, 0]
        idx = jnp.minimum(t.cols, v.shape[0] - 1)
        keepmask = t.valid_mask() & keep(t.vals, v[idx])
        s = t._select(keepmask)
        return (
            s.rows[None, None, None], s.cols[None, None, None],
            s.vals[None, None, None], s.nnz[None, None, None],
        )

    r, c, v, n = jax.shard_map(
        body,
        mesh=A3.grid.mesh,
        in_specs=(TILE3_SPEC,) * 4 + (COLVEC3_SPEC,),
        out_specs=(TILE3_SPEC,) * 4,
        check_vma=False,
    )(A3.rows, A3.cols, A3.vals, A3.nnz, colvec)
    return dataclasses.replace(A3, rows=r, cols=c, vals=v, nnz=n)


@partial(jax.jit, static_argnames=("pred",))
def prune3d(A3: SpParMat3D, pred) -> SpParMat3D:
    """Drop entries where ``pred(val)`` (≈ SpParMat::Prune)."""

    def body(rows, cols, vals, nnz):
        t = A3.local_tile(rows, cols, vals, nnz)
        s = t._select(t.valid_mask() & ~pred(t.vals))
        return (
            s.rows[None, None, None], s.cols[None, None, None],
            s.vals[None, None, None], s.nnz[None, None, None],
        )

    r, c, v, n = jax.shard_map(
        body,
        mesh=A3.grid.mesh,
        in_specs=(TILE3_SPEC,) * 4,
        out_specs=(TILE3_SPEC,) * 4,
        check_vma=False,
    )(A3.rows, A3.cols, A3.vals, A3.nnz)
    return dataclasses.replace(A3, rows=r, cols=c, vals=v, nnz=n)


@partial(jax.jit, static_argnames=("fn",))
def apply3d(A3: SpParMat3D, fn) -> SpParMat3D:
    """Elementwise value transform (≈ SpParMat::Apply)."""
    valid = A3.rows < A3.tile_rows
    return dataclasses.replace(
        A3, vals=jnp.where(valid, fn(A3.vals), A3.vals)
    )


@partial(jax.jit, static_argnames=("fn",))
def dim_apply3d_cols(A3: SpParMat3D, colvec: Array, fn) -> SpParMat3D:
    """vals[i,j] = fn(vals[i,j], colvec[j]) (≈ SpParMat::DimApply(Column))."""
    _check_colsplit(A3)

    def body(rows, cols, vals, nnz, vblk):
        t = A3.local_tile(rows, cols, vals, nnz)
        v = vblk[0, 0]
        vpad = jnp.concatenate([v, jnp.zeros((1,), v.dtype)])
        idx = jnp.minimum(t.cols, v.shape[0])
        new_vals = jnp.where(t.valid_mask(), fn(t.vals, vpad[idx]), t.vals)
        return (
            t.rows[None, None, None], t.cols[None, None, None],
            new_vals[None, None, None], t.nnz[None, None, None],
        )

    r, c, v, n = jax.shard_map(
        body,
        mesh=A3.grid.mesh,
        in_specs=(TILE3_SPEC,) * 4 + (COLVEC3_SPEC,),
        out_specs=(TILE3_SPEC,) * 4,
        check_vma=False,
    )(A3.rows, A3.cols, A3.vals, A3.nnz, colvec)
    return dataclasses.replace(A3, rows=r, cols=c, vals=v, nnz=n)


def resplit3d_fixed(
    A3: SpParMat3D, split: str, *, stage_capacity: int, tile_capacity: int
) -> tuple[SpParMat3D, Array]:
    """``resplit3d`` with CALLER-FROZEN capacities and no host sizing or
    retry: returns (converted matrix, device scalar dropped-tuple count).

    The zero-readback building block for iteration blocks (MCL
    ``chaos_every``): the caller checks ``dropped`` at its sync point and
    rerolls with bigger capacities instead of this function reading back
    per call."""
    if A3.split == split:
        return A3, jnp.zeros((), jnp.int32)
    gr, gc, gv = _globalize3d(A3)
    return redistribute_coo3d(
        A3.grid, gr, gc, gv, A3.nrows, A3.ncols, split=split,
        stage_capacity=stage_capacity, tile_capacity=tile_capacity,
    )


def resplit3d(A3: SpParMat3D, split: str, *, slack: float = 2.0,
              max_retries: int = 3) -> SpParMat3D:
    """Convert between col-split and row-split layouts on the same 3D grid
    (the orientation change MemEfficientSpGEMM3D needs between iterations:
    SUMMA3D consumes A col-split x B row-split and produces col-split).

    Globalize + 3-hop reroute; same engine as the 2D<->3D conversions.
    """
    if A3.split == split:
        return A3
    gr, gc, gv = _globalize3d(A3)
    total = int(np.asarray(jnp.sum(A3.nnz)))
    g3 = A3.grid

    def route(stage_cap, tile_cap):
        return redistribute_coo3d(
            g3, gr, gc, gv, A3.nrows, A3.ncols, split=split,
            stage_capacity=stage_cap, tile_capacity=tile_cap,
        )

    return _route_with_retry(
        route, gr.shape[-1], (g3.pc, g3.pr, g3.layers), total,
        g3.layers * g3.pr * g3.pc, slack, max_retries, "3D resplit",
    )
