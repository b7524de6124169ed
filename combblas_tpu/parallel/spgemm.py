"""Distributed SpGEMM: SUMMA over the device mesh (≈ ParFriends Mult_AnXBn_*).

The reference's baseline ``Mult_AnXBn_Synch`` (``ParFriends.h:1005-1108``)
runs √p stages; each stage broadcasts one A-block along the process row and
one B-block along the process column (``SpParHelper::BCastMatrix``), does a
local hash SpGEMM, and finally k-way-merges the √p stage outputs
(``MultiwayMerge.h:412``).

TPU-native schedule: the per-stage broadcasts collapse into ONE ``all_gather``
of the A-tiles over the ``"c"`` axis and of the B-tiles over the ``"r"`` axis
(same total bytes as the √p broadcasts, but a single fused ICI collective
that XLA can software-pipeline), then a static python loop over stages feeds
the local ESC kernel, and the merge is a single concat + sort + segmented
fold — the MultiwayMerge heap becomes the TPU's native sort.  The
double-buffered / overlapped variants (``ParFriends.h:799,1111``) are
subsumed: XLA overlaps the gather with the first stages automatically.

A ring variant (lower peak memory, ≈ SUMMA with in-place rotation à la
``BFSFriends``' carousel) swaps the all_gather for per-stage ``ppermute``;
see ``ring=True``.

Capacity model (the static-shape analog of ``EstimateFLOP`` /
``EstPerProcessNnzSUMMA``, ``ParFriends.h:356-448,1243-1349``): callers pass
``flop_capacity`` (per stage, per tile) and ``out_capacity`` (final tile
nnz), or use ``summa_capacities`` to measure them exactly with a cheap
distributed symbolic pass before jitting the numeric one.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import obs
from ..ops.compressed import CSR
from ..ops.spgemm import expand as esc_expand
from ..ops.tuples import SpTuples
from ..semiring import Semiring
from .grid import COL_AXIS, ROW_AXIS
from .spmat import TILE_SPEC, SpParMat


def host_value(x) -> np.ndarray:
    """Host numpy value of a FULLY-REPLICATED global array, multi-host
    safe: under multi-process JAX a replicated array still "spans"
    non-addressable devices, so read one addressable shard (each holds
    the whole array when the producing shard_map used ``out_specs=P()``).
    """
    if jax.process_count() > 1:
        return np.asarray(x.addressable_shards[0].data)
    return np.asarray(x)


def _publish_opnames(fn, *args, nth: int | None = None, **kw) -> None:
    """With telemetry on, and once a program (a jitted function, its
    operands' shapes and its static arguments): publish the compiled
    program's ``{instruction: op_name}`` table (``obs.opnames``), which
    is what lets a device trace's operations be read by ``SQ_SCOPES``.
    Called AFTER the launch, as ``models/tc.py:tc_job`` publishes: the
    first traced call pays for the program as an untraced one does, and
    this lowers it again and fetches it from the compile cache.
    ``nth``: see ``obs.opnames.publish``."""
    if not obs.ENABLED:
        return
    key = (fn.__name__, nth) + tuple(
        (leaf.shape, str(leaf.dtype)) if hasattr(leaf, "shape") else leaf
        for leaf in jax.tree.leaves((args, kw))
    )
    obs.opnames.publish_once(
        key, lambda: fn.lower(*args, **kw).compile().as_text(), nth)


def _check_compat(A: SpParMat, B: SpParMat):
    """≈ CheckSpGEMMCompliance + ProductGrid (ParFriends.h:161,
    CommGrid.cpp:164)."""
    assert A.grid == B.grid, "A and B must share a grid"
    assert A.grid.is_square, "SUMMA requires a square grid (pr == pc)"
    assert A.ncols == B.nrows, f"dim mismatch {A.ncols} != {B.nrows}"
    assert A.grid.local_cols(A.ncols) == A.grid.local_rows(B.nrows), (
        "A col-blocking must equal B row-blocking"
    )


def _gather_stage_tiles(t: SpTuples, axis_name, p: int) -> list[SpTuples]:
    """All-gather a tile's arrays over a mesh axis → one SpTuples per stage.

    The fused-collective replacement for the reference's per-stage
    ``SpParHelper::BCastMatrix`` loop.
    """
    with jax.named_scope("sq.exchange"):
        g = [
            lax.all_gather(x, axis_name)
            for x in (t.rows, t.cols, t.vals, t.nnz)
        ]
    return [
        SpTuples(
            rows=g[0][s], cols=g[1][s], vals=g[2][s], nnz=g[3][s],
            nrows=t.nrows, ncols=t.ncols,
        )
        for s in range(p)
    ]


def _carousel_perms(p: int):
    """Cannon-carousel permutation tables over the joint (row, col) axis:
    (skew_a, skew_b, rot_a, rot_b).  Pre-skew puts A_{i,(i+j)%p} /
    B_{(i+j)%p,j} on device (i, j) so both held tiles share the
    contraction index k=(i+j+s)%p at stage s; the rotations shift A left
    / B up one neighbor per stage (the ring schedule of the reference's
    carousel, BitMapCarousel.h)."""
    skew_a = [
        (i * p + (i + j) % p, i * p + j)
        for i in range(p) for j in range(p)
    ]
    skew_b = [
        (((i + j) % p) * p + j, i * p + j)
        for i in range(p) for j in range(p)
    ]
    rot_a = [
        (i * p + (j + 1) % p, i * p + j)
        for i in range(p) for j in range(p)
    ]
    rot_b = [
        (((i + 1) % p) * p + j, i * p + j)
        for i in range(p) for j in range(p)
    ]
    return skew_a, skew_b, rot_a, rot_b


def _rotate_tiles(t: SpTuples, perm) -> SpTuples:
    """One carousel hop: ``ppermute`` all four tile arrays over the joint
    (row, col) mesh axis.  Shared by the ESC, scan, and windowed carousel
    paths (this used to be duplicated as a local ``joint_permute`` in
    each ring kernel)."""
    with jax.named_scope("sq.exchange"):
        return SpTuples(
            rows=lax.ppermute(t.rows, (ROW_AXIS, COL_AXIS), perm),
            cols=lax.ppermute(t.cols, (ROW_AXIS, COL_AXIS), perm),
            vals=lax.ppermute(t.vals, (ROW_AXIS, COL_AXIS), perm),
            nnz=lax.ppermute(t.nnz, (ROW_AXIS, COL_AXIS), perm),
            nrows=t.nrows, ncols=t.ncols,
        )


def _chain_tiles(t: SpTuples, dep) -> SpTuples:
    """Pin a schedule dependency: the returned tile's arrays cannot be
    consumed — so the NEXT rotation cannot be issued — before ``dep``
    (an array from the current stage's accumulate) has been computed.
    This is the explicit rotate→compute→rotate serial chain of the
    UNPIPELINED carousel, kept as the measurement control
    (``pipeline=False``); the pipelined schedule never calls this, so
    its next-stage ``ppermute`` is free to overlap the current stage's
    compute."""
    rows, cols, vals, nnz, _ = lax.optimization_barrier(
        (t.rows, t.cols, t.vals, t.nnz, dep)
    )
    return dataclasses.replace(t, rows=rows, cols=cols, vals=vals, nnz=nnz)


def _carousel_stages(a_mine: SpTuples, b_mine: SpTuples, p: int):
    """Generator driving the STAGE-PIPELINED carousel schedule: yields
    ``(s, a_stage, b_stage)`` for each of the ``p`` stages with the
    operands held in TWO-SLOT buffers.  The rotation producing stage
    ``s+1``'s tiles is issued BEFORE stage ``s``'s tiles are consumed
    (the yield), so XLA's latency-hiding scheduler can overlap the
    neighbor ICI traffic with the stage's accumulate.  A serial
    (unpipelined) control needs more than trace order — the rotation
    must be PINNED behind the accumulate with ``_chain_tiles``, which
    needs a stage-output array and so lives in the kernel's own loop
    (see ``_windowed_carousel_compute``); the ESC/scan rings using this
    generator are always pipelined."""
    skew_a, skew_b, rot_a, rot_b = _carousel_perms(p)
    a_cur = _rotate_tiles(a_mine, skew_a)
    b_cur = _rotate_tiles(b_mine, skew_b)
    for s in range(p):
        a_nxt = b_nxt = None
        if s != p - 1:
            a_nxt = _rotate_tiles(a_cur, rot_a)
            b_nxt = _rotate_tiles(b_cur, rot_b)
        yield s, a_cur, b_cur
        if s != p - 1:
            a_cur, b_cur = a_nxt, b_nxt


def _carousel_stages_pair(a_mine: SpTuples, x_mine, p: int, *,
                          pipeline: bool = True, dep=None):
    """Carousel schedule for a (sparse tile, DENSE panel) operand pair
    — the SpMM twin of ``_carousel_stages``: A rides ``_rotate_tiles``,
    the dense feature panel rides a plain joint-axis ``ppermute``.
    ``pipeline=True`` issues the rotation producing stage ``s+1``'s
    operands BEFORE stage ``s``'s are consumed (two-slot buffers, the
    r9 overlap schedule).  ``pipeline=False`` is the serial control:
    the next rotation is PINNED behind the caller's accumulate via
    ``dep`` (a zero-arg callable returning a stage-output array,
    evaluated after the caller's loop body ran — the generator resumes
    only on the next iteration request)."""
    skew_a, skew_b, rot_a, rot_b = _carousel_perms(p)
    a_cur = _rotate_tiles(a_mine, skew_a)
    x_cur = lax.ppermute(x_mine, (ROW_AXIS, COL_AXIS), skew_b)
    for s in range(p):
        a_nxt = x_nxt = None
        if pipeline and s != p - 1:
            a_nxt = _rotate_tiles(a_cur, rot_a)
            x_nxt = lax.ppermute(x_cur, (ROW_AXIS, COL_AXIS), rot_b)
        yield s, a_cur, x_cur
        if s != p - 1:
            if not pipeline:
                d = dep() if dep is not None else a_cur.nnz
                a_pin = _chain_tiles(a_cur, d)
                x_pin, _ = lax.optimization_barrier((x_cur, d))
                a_nxt = _rotate_tiles(a_pin, rot_a)
                x_nxt = lax.ppermute(x_pin, (ROW_AXIS, COL_AXIS), rot_b)
            a_cur, x_cur = a_nxt, x_nxt


@partial(
    jax.jit,
    static_argnames=("sr", "flop_capacity", "out_capacity", "ring",
                     "merge"),
)
def summa_spgemm(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    flop_capacity: int,
    out_capacity: int,
    ring: bool = False,
    merge: str = "sort",
) -> SpParMat:
    """C = A ⊗ B over the grid.

    ``flop_capacity`` bounds ONE stage's expansion on one tile;
    ``out_capacity`` bounds the final per-tile nnz.

    ``merge`` picks the stage-chunk combine (round 13): ``"sort"`` is
    the classic concat + full ``lax.sort`` compact; ``"runs"`` sorts
    each STAGE chunk individually (p sorts of flop_capacity — strictly
    less sort work than one sort of p·flop_capacity) and k-way merges
    the sorted runs by rank-space union
    (``ops.spgemm.merge_sorted_runs``), so the compact skips its sort
    entirely.  Bit-exact with ``"sort"`` for every semiring (ties keep
    stage order).
    """
    _check_compat(A, B)
    assert merge in ("sort", "runs"), merge
    grid = A.grid
    p = grid.pr
    if obs.ENABLED:
        # trace-time only (this fn is jitted): counts (re)traces per
        # static config, never executions — the jit retrace visibility
        obs.count("trace.summa_spgemm", ring=ring, merge=merge)
        if ring and p > 1:
            obs.count("spgemm.pipeline.stages_overlapped", p - 1)

    def body(ar, ac, av, an, br, bc, bv, bn):
        from ..ops.spgemm import merge_sorted_runs

        # stitch local tiles
        a_mine = A.local_tile(ar, ac, av, an)
        b_mine = B.local_tile(br, bc, bv, bn)

        def stage_output(a_stage: SpTuples, b_stage: SpTuples) -> SpTuples:
            with jax.named_scope("sq.densify"):
                b_csr = CSR.from_tuples(b_stage)
            with jax.named_scope("sq.dot"):
                return esc_expand(sr, a_stage, b_csr, flop_capacity)

        chunks = []
        if not ring:
            # A-tiles of my grid row / B-tiles of my grid column.
            a_stages = _gather_stage_tiles(a_mine, COL_AXIS, p)
            b_stages = _gather_stage_tiles(b_mine, ROW_AXIS, p)
            for s in range(p):
                chunks.append(stage_output(a_stages[s], b_stages[s]))
        else:
            # Cannon's algorithm: O(capacity) peak memory instead of
            # O(p·capacity), STAGE-PIPELINED — ``_carousel_stages``
            # issues the ppermute producing stage s+1's tiles before
            # stage s's tiles are consumed (two-slot operand buffers),
            # so the neighbor ICI rotation overlaps the local expand
            # instead of the old rotate→compute→rotate serial chain.
            for s, a_cur, b_cur in _carousel_stages(a_mine, b_mine, p):
                chunks.append(stage_output(a_cur, b_cur))

        with jax.named_scope("sq.extract"):
            if merge == "runs":
                # per-stage sorts + rank-space union: the stage chunks
                # ARE the sorted runs, so the compact skips its global
                # sort
                merged = merge_sorted_runs(
                    [ch.sort_rowmajor() for ch in chunks]
                )
                out = merged.compact(
                    sr, capacity=out_capacity, assume_sorted=True
                )
            else:
                merged = SpTuples.concat(chunks)
                out = merged.compact(sr, capacity=out_capacity)
        return SpParMat._pack_tile(out)

    r, c, v, n = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 8,
        out_specs=(TILE_SPEC,) * 4,
        check_vma=False,
    )(A.rows, A.cols, A.vals, A.nnz, B.rows, B.cols, B.vals, B.nnz)
    return SpParMat(
        rows=r, cols=c, vals=v, nnz=n,
        nrows=A.nrows, ncols=B.ncols, grid=grid,
    )


@partial(jax.jit, static_argnames=("padded",))
def summa_stage_flops(A: SpParMat, B: SpParMat, padded: bool = True) -> jax.Array:
    """[p, pr, pc] float32 flop count per stage per output tile.

    The distributed symbolic pass (≈ EstimateFLOP, ParFriends.h:356-448).
    Values only (no ``vals`` arrays) cross the ICI: flop counting needs A's
    (rows, cols) for validity/contraction ids and B's rows for row lengths.

    ``padded=True`` (the default) counts CHUNKED-EXPANSION SLOTS — each
    A-entry's B-row walk rounded up to ``ops.spgemm.CHUNK_W`` lanes, the
    capacity the expand kernel actually allocates; ``padded=False`` gives
    true scalar multiplies (EstimateFLOP parity, for reporting).
    """
    from ..ops.spgemm import CHUNK_W

    _check_compat(A, B)
    grid = A.grid
    p = grid.pr
    lrB = B.local_rows

    def body(ar, ac, br):
        a_rows, a_cols = ar[0, 0], ac[0, 0]
        b_rows = br[0, 0]
        ag_rows = lax.all_gather(a_rows, COL_AXIS)
        ag_cols = lax.all_gather(a_cols, COL_AXIS)
        bg_rows = lax.all_gather(b_rows, ROW_AXIS)
        per_stage = []
        with jax.named_scope("sq.symbolic"):
            for s in range(p):
                b_valid = bg_rows[s] < lrB
                blens = jax.ops.segment_sum(
                    b_valid.astype(jnp.int32), bg_rows[s],
                    num_segments=lrB + 1,
                )
                if padded:
                    blens = -(-blens // CHUNK_W) * CHUNK_W
                a_valid = ag_rows[s] < A.local_rows
                k = jnp.minimum(ag_cols[s], lrB)
                per_entry = jnp.where(a_valid, blens[k], 0)
                per_stage.append(jnp.sum(per_entry.astype(jnp.float32)))
        mine = jnp.stack(per_stage)  # [p]
        # Replicate the (tiny) result so every PROCESS can read it whole —
        # a mesh-sharded output is not host-addressable under multi-host
        # (sizing does np.asarray on it, tests/_multihost_worker.py).
        g = lax.all_gather(lax.all_gather(mine, COL_AXIS), ROW_AXIS)
        return jnp.transpose(g, (2, 0, 1))  # [p, pr, pc]

    return jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 3,
        out_specs=P(),
        check_vma=False,
    )(A.rows, A.cols, B.rows)


def _caps_from_stage_flops(per_stage: np.ndarray, dense_tile: int,
                           slack: float):
    flop_cap = max(int(per_stage.max() * slack) + 1, 1)
    total_per_tile = per_stage.sum(axis=0).max()
    out_cap = max(min(int(total_per_tile * slack) + 1, dense_tile), 1)
    return flop_cap, out_cap


def summa_capacities(A: SpParMat, B: SpParMat, slack: float = 1.05):
    """Host helper: symbolic pass → (flop_capacity, out_capacity).

    flop_capacity = max single-stage single-tile expansion; out_capacity =
    max per-tile total flops (a product has at most one output per flop),
    clamped to the dense tile size. ``slack`` covers the float32 rounding of
    the counts plus headroom for reusing compiled code across inputs.

    NOTE: reads the device symbolic pass back to host (one sync).
    """
    per_stage = host_value(summa_stage_flops(A, B)).astype(np.float64)
    if obs.ENABLED:
        _record_symbolic_metrics(per_stage)
    return _caps_from_stage_flops(
        per_stage, A.local_rows * B.local_cols, slack
    )


def _record_symbolic_metrics(per_stage: np.ndarray) -> None:
    """Registry facts from one symbolic pass: total symbolic fill-in
    (expansion slots — the flops-side of symbolic-vs-realized) and the
    per-tile LoadImbalance (max/mean over output tiles, the reference's
    ``LoadImbalance`` statistic)."""
    per_tile = per_stage.sum(axis=0)
    mean = float(per_tile.mean())
    obs.count("spgemm.symbolic_fill_slots", float(per_stage.sum()))
    obs.gauge(
        "spgemm.load_imbalance",
        float(per_tile.max() / mean) if mean > 0 else 1.0,
    )


def summa_rowblock_flops(
    A: SpParMat, B: SpParMat, block_rows: int, chunk_w: int = 0
) -> jax.Array:
    """[nblocks, p, pr, pc] float32 flop counts resolved by A ROW BLOCK —
    the symbolic pass that drives the windowed tier's per-block sizing
    and its skip list (a block with zero flops has zero output and is
    never scanned).

    ``chunk_w > 0`` counts chunked-expansion SLOTS (each B-row walk
    rounded up to ``chunk_w`` lanes — the capacity the windowed tier's
    expansion actually allocates, exact by the ``flops_padded``
    argument); ``chunk_w == 0`` counts true scalar multiplies (the
    ``estimate_nnz_upper``-style output bound).  Thin slice of the
    one-pass ``summa_rowblock_flops_pair`` (chunk_w=1 padding is the
    identity, so index 1 of the pair is always the true count).
    """
    pair = summa_rowblock_flops_pair(
        A, B, block_rows, chunk_w=max(chunk_w, 1)
    )
    return pair[0] if chunk_w else pair[1]


@partial(jax.jit, static_argnames=("block_rows", "chunk_w"))
def summa_rowblock_flops_pair(
    A: SpParMat, B: SpParMat, block_rows: int, chunk_w: int
) -> jax.Array:
    """[2, nblocks, p, pr, pc]: the ``chunk_w``-padded counts (index 0)
    and the true counts (index 1) from ONE symbolic pass — the sizing
    entry pays the all_gathers and segment sums once instead of running
    ``summa_rowblock_flops`` twice."""
    _check_compat(A, B)
    grid = A.grid
    p = grid.pr
    lrA = A.local_rows
    lrB = B.local_rows
    nblocks = -(-lrA // block_rows)

    def body(ar, ac, br):
        a_rows, a_cols = ar[0, 0], ac[0, 0]
        b_rows = br[0, 0]
        ag_rows = lax.all_gather(a_rows, COL_AXIS)
        ag_cols = lax.all_gather(a_cols, COL_AXIS)
        bg_rows = lax.all_gather(b_rows, ROW_AXIS)
        per_stage = []
        with jax.named_scope("sq.symbolic"):
            for s in range(p):
                b_valid = bg_rows[s] < lrB
                blens = jax.ops.segment_sum(
                    b_valid.astype(jnp.int32), bg_rows[s],
                    num_segments=lrB + 1,
                )
                blens_pad = -(-blens // chunk_w) * chunk_w
                a_valid = ag_rows[s] < lrA
                k = jnp.minimum(ag_cols[s], lrB)
                g = jnp.where(a_valid, ag_rows[s] // block_rows, nblocks)
                both = []
                for bl in (blens_pad, blens):
                    per_entry = jnp.where(
                        a_valid, bl[k], 0).astype(jnp.float32)
                    both.append(
                        jax.ops.segment_sum(
                            per_entry, g, num_segments=nblocks + 1
                        )[:nblocks]
                    )
                per_stage.append(jnp.stack(both))  # [2, nblocks]
        mine = jnp.stack(per_stage)  # [p, 2, nblocks]
        g2 = lax.all_gather(lax.all_gather(mine, COL_AXIS), ROW_AXIS)
        return jnp.transpose(g2, (3, 4, 2, 0, 1))  # [2, nblocks, p, pr, pc]

    return jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 3,
        out_specs=P(),
        check_vma=False,
    )(A.rows, A.cols, B.rows)


def _window_stage_symbolic(
    a_rows_s, a_cols_s, b_rows_s, b_cols_s,
    lrA: int, lrB: int, block_rows: int, block_cols: int,
    nblocks: int, ncw: int, chunk_w: int,
):
    """One SUMMA stage's [2, nblocks, ncw] windowed symbolic counts
    (index 0 chunk-padded, index 1 true) from the stage's gathered A/B
    index arrays — the inner kernel of ``summa_window_flops_pair``,
    shared with the per-layer 3D pass (``mesh3d.
    summa3d_window_flops_pair``)."""
    b_valid = b_rows_s < lrB
    # per-(col-window, B-row) walk lengths; invalid entries fall in the
    # ncw overflow bucket (a sentinel col == lcB would otherwise land in
    # the last window when block_cols ∤ lcB)
    h = jnp.where(
        b_valid, b_cols_s // block_cols, ncw
    ).astype(jnp.int32)
    key = h * (lrB + 1) + jnp.minimum(b_rows_s, lrB)
    blens2 = jax.ops.segment_sum(
        b_valid.astype(jnp.int32), key,
        num_segments=(ncw + 1) * (lrB + 1),
    ).reshape(ncw + 1, lrB + 1)
    a_valid = a_rows_s < lrA
    k = jnp.minimum(a_cols_s, lrB)
    g = jnp.where(a_valid, a_rows_s // block_rows, nblocks)
    # chunk_w == 1 padding is the identity: run the inner gather+segment
    # loop once and reuse it for both variants (the dot-backend sizing
    # path never consumes the padded counts, so it requests chunk_w=1)
    variants = (
        (blens2,) if chunk_w == 1
        else (-(-blens2 // chunk_w) * chunk_w, blens2)
    )
    both = []
    for bl in variants:
        per_h = []
        for hh in range(ncw):  # static loop bounds memory to
            per_entry = jnp.where(  # one [nnzA] gather per window
                a_valid, bl[hh, k], 0
            ).astype(jnp.float32)
            per_h.append(
                jax.ops.segment_sum(
                    per_entry, g, num_segments=nblocks + 1
                )[:nblocks]
            )
        both.append(jnp.stack(per_h, axis=1))  # [nblocks, ncw]
    if len(both) == 1:
        both = [both[0], both[0]]
    return jnp.stack(both)  # [2, nblocks, ncw]


@partial(
    jax.jit, static_argnames=("block_rows", "block_cols", "chunk_w")
)
def summa_window_flops_pair(
    A: SpParMat, B: SpParMat, block_rows: int, block_cols: int,
    chunk_w: int = 1,
) -> jax.Array:
    """[2, nblocks, ncolwin, p, pr, pc]: the 2D-resolved symbolic pass —
    flop counts per (A row block, B col window) per stage per output
    tile; index 0 is ``chunk_w``-padded, index 1 the true counts (one
    pass, like ``summa_rowblock_flops_pair``).

    This is what sizes the 2D ``dot`` backend: per-window output bounds
    and the 2D skip list (a window with zero symbolic flops produces
    nothing — its stage matmuls and its extraction scan are both
    elided at trace time).
    """
    _check_compat(A, B)
    grid = A.grid
    p = grid.pr
    lrA = A.local_rows
    lrB, lcB = B.local_rows, B.local_cols
    nblocks = -(-lrA // block_rows)
    ncw = -(-lcB // block_cols)

    def body(ar, ac, br, bc):
        a_rows, a_cols = ar[0, 0], ac[0, 0]
        b_rows, b_cols = br[0, 0], bc[0, 0]
        ag_rows = lax.all_gather(a_rows, COL_AXIS)
        ag_cols = lax.all_gather(a_cols, COL_AXIS)
        bg_rows = lax.all_gather(b_rows, ROW_AXIS)
        bg_cols = lax.all_gather(b_cols, ROW_AXIS)
        with jax.named_scope("sq.symbolic"):
            per_stage = [
                _window_stage_symbolic(
                    ag_rows[s], ag_cols[s], bg_rows[s], bg_cols[s],
                    lrA, lrB, block_rows, block_cols, nblocks, ncw,
                    chunk_w,
                )
                for s in range(p)
            ]
        mine = jnp.stack(per_stage)  # [p, 2, nblocks, ncw]
        g2 = lax.all_gather(lax.all_gather(mine, COL_AXIS), ROW_AXIS)
        # [pr, pc, p, 2, nblocks, ncw] -> [2, nblocks, ncw, p, pr, pc]
        return jnp.transpose(g2, (3, 4, 5, 2, 0, 1))

    return jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 4,
        out_specs=P(),
        check_vma=False,
    )(A.rows, A.cols, B.rows, B.cols)


@partial(jax.jit, static_argnames=("block_cols",))
def summa_window_bnnz(B: SpParMat, block_cols: int) -> jax.Array:
    """[pr, pc, ncolwin] int32, replicated: B-tile nnz per col window —
    the static gather capacity of the 2D dot backend's CSC panel slices
    (``panel_cap`` = global max)."""
    lrB, lcB = B.local_rows, B.local_cols
    ncw = -(-lcB // block_cols)

    def body(br, bc):
        b_rows, b_cols = br[0, 0], bc[0, 0]
        with jax.named_scope("sq.symbolic"):
            valid = b_rows < lrB
            h = jnp.where(
                valid, b_cols // block_cols, ncw).astype(jnp.int32)
            mine = jax.ops.segment_sum(
                valid.astype(jnp.int32), h, num_segments=ncw + 1
            )[:ncw]
        g2 = lax.all_gather(lax.all_gather(mine, COL_AXIS), ROW_AXIS)
        return g2  # [pr, pc, ncw]

    return jax.shard_map(
        body,
        mesh=B.grid.mesh,
        in_specs=(TILE_SPEC,) * 2,
        out_specs=P(),
        check_vma=False,
    )(B.rows, B.cols)


def windowed_plan_2d(
    per_window_padded: np.ndarray | None,
    per_window_true: np.ndarray,
    block_rows: int,
    block_cols: int,
    local_rows: int,
    local_cols_b: int,
    slack: float = 1.02,
) -> tuple[tuple, tuple, tuple]:
    """2D twin of ``windowed_plan``: per-(row-block, col-window) static
    (flop_caps, out_caps, skip), each a tuple of per-block tuples.

    Out caps are the clamped-flops bound per WINDOW (true per-tile
    window flops, max over tiles, clamped by the window's dense cells);
    a window whose symbolic count is zero is skipped — its stage
    matmuls, its B panel, and its extraction scan are never emitted.
    ``per_window_padded`` may be ``None``: the ``dot`` backend does no
    chunked expansion, so its flop caps are never consumed — passing
    None (all-ones caps) saves the padded symbolic pass entirely (the
    device pair computes both in one pass; the HOST sizing path has to
    run one einsum per variant, so benchmarks skip the dead one).
    """
    pt = np.asarray(per_window_true, np.float64)
    pb = (
        None if per_window_padded is None
        else np.asarray(per_window_padded, np.float64)
    )
    nblocks, ncw = pt.shape[0], pt.shape[1]
    flop_caps, out_caps, skip = [], [], []
    for g in range(nblocks):
        rb = min(block_rows, local_rows - g * block_rows)
        fr, orow, sr_ = [], [], []
        for h in range(ncw):
            wc = min(block_cols, local_cols_b - h * block_cols)
            cells = rb * wc
            tot = pt[g, h].sum(axis=0).max()  # per-tile total, max
            sr_.append(bool(tot <= 0))
            fr.append(
                1 if pb is None
                else max(int(pb[g, h].max() * slack) + 1, 1)
            )
            orow.append(max(min(int(tot * slack) + 1, cells), 1))
        flop_caps.append(tuple(fr))
        out_caps.append(tuple(orow))
        skip.append(tuple(sr_))
    return tuple(flop_caps), tuple(out_caps), tuple(skip)


def windowed_plan(
    per_block_padded: np.ndarray,
    per_block_true: np.ndarray,
    block_rows: int,
    local_rows: int,
    local_cols_b: int,
    slack: float = 1.02,
) -> tuple[tuple[int, ...], tuple[int, ...], tuple[bool, ...]]:
    """Derive the windowed tier's static plan from the two symbolic
    passes: per-block expansion capacities (max over stages and tiles of
    the chunk-padded counts), per-block output capacities (the
    ``estimate_nnz_upper`` bound — per-tile true flops clamped by the
    dense block, max over tiles), and the SKIP LIST (blocks whose
    symbolic flop count is zero produce nothing and are never scanned).

    ``slack`` covers float32 rounding when the counts come from the
    device symbolic pass (the host pass is float64-exact; the padded
    counts are exact by the ``flops_padded`` argument either way).
    """
    pb = np.asarray(per_block_padded, np.float64)
    pt = np.asarray(per_block_true, np.float64)
    nblocks = pb.shape[0]
    flop_caps, out_caps, skip = [], [], []
    for g in range(nblocks):
        rb = min(block_rows, local_rows - g * block_rows)
        cells = rb * local_cols_b
        fmax = pb[g].max()
        tot = pt[g].sum(axis=0).max()  # per-tile total, max over tiles
        skip.append(bool(tot <= 0))
        flop_caps.append(max(int(fmax * slack) + 1, 1))
        out_caps.append(max(min(int(tot * slack) + 1, cells), 1))
    return tuple(flop_caps), tuple(out_caps), tuple(skip)


def packed_windows(skip) -> tuple[int, ...]:
    """1D skip list → dense LAUNCH LIST of occupied row blocks.

    The kernels iterate this packed list instead of the full block grid
    with per-block skip tests, so a sparse plan pays one launch per
    OCCUPIED block — the trace-level contract the oracle seeding
    tightens (`_oracle_out_caps_2d` turns flops-positive but
    output-empty windows into skips, which packing then never visits).
    """
    return tuple(g for g, s in enumerate(skip) if not s)


def packed_windows_2d(skip) -> tuple[tuple[int, int], ...]:
    """2D skip list → packed launch list of occupied (row block, col
    window) pairs, block-major then window-major — the kernels' output
    chunk order, so a packed run and a skip-list run emit IDENTICAL
    tiles."""
    return tuple(
        (g, h) for g, row in enumerate(skip)
        for h, s in enumerate(row) if not s
    )


def _live_windows_by_block(skip) -> tuple:
    """Packed 2D launch list grouped by row block:
    ``((g, (h, ...)), ...)`` — blocks with no live window are absent
    entirely (their A block is never masked or densified)."""
    out = []
    for g, row in enumerate(skip):
        hs = tuple(h for h, s in enumerate(row) if not s)
        if hs:
            out.append((g, hs))
    return tuple(out)


def _extract_window_2d(acc, zero, lo, h, rb, block_cols, lrA, lcB, out_cap):
    """One (row block, col window) extraction → (global-coord chunk,
    overflow vs the symbolic bound).  Shared by the gathered and
    carousel schedules (and the 3D per-layer kernel)."""
    from ..ops.spgemm import sparsify_windowed

    wc = min(block_cols, lcB - h * block_cols)
    t_blk, total = sparsify_windowed(acc, zero, rb, wc, out_cap)
    vm = t_blk.valid_mask()
    chunk = SpTuples(
        rows=jnp.where(vm, t_blk.rows + lo, lrA),
        cols=jnp.where(vm, t_blk.cols + h * block_cols, lcB),
        vals=t_blk.vals, nnz=t_blk.nnz, nrows=lrA, ncols=lcB,
    )
    return chunk, total - out_cap


def _extract_block_1d(acc, zero, lo, rb, lrA, lcB, out_cap):
    """One full-width row-block extraction → (chunk, overflow)."""
    from ..ops.spgemm import sparsify_windowed

    t_blk, total = sparsify_windowed(acc, zero, rb, lcB, out_cap)
    rows = jnp.where(t_blk.valid_mask(), t_blk.rows + lo, lrA)
    chunk = SpTuples(
        rows=rows, cols=t_blk.cols, vals=t_blk.vals,
        nnz=t_blk.nnz, nrows=lrA, ncols=lcB,
    )
    return chunk, total - out_cap


def _shift_rowblock(am: SpTuples, lo, arows: int) -> SpTuples:
    """Row-block tile → block-local coordinates: valid rows shift down
    by ``lo``; invalid slots land EXACTLY at the new sentinel ``arows``
    (= the padded block height) so ``valid_mask`` stays false after the
    ``nrows`` rewrite.  Shared by the fused and local dot kernels."""
    import dataclasses as _dc

    valid = am.valid_mask()
    a_loc = _dc.replace(am, rows=jnp.where(valid, am.rows - lo, arows))
    return _dc.replace(a_loc, nrows=arows)


def _dense_col_panel(
    sr: Semiring, bs: SpTuples, starts, h: int, block_cols: int,
    pk: int, pwin: int, panel_cap: int,
):
    """Dense [pk, pwin] panel of B col window ``h`` from the col-major-
    sorted stage tile ``bs``: the window's entries occupy one contiguous
    CSC slot range [starts[h], starts[h+1]), gathered with a static
    ``panel_cap``-slot slice and scattered with the semiring combiner —
    O(panel_cap) work per window (not O(nnz)), duplicate-entry safe.
    This is the stage operand of the 2D ``dot`` backend: peak memory
    pk × pwin cells, bounded by the column window instead of B's tile
    width."""
    from ..ops.spgemm import scatter_combine_for

    start = starts[h]
    idx = start + jnp.arange(panel_cap, dtype=jnp.int32)
    ok = idx < starts[h + 1]
    ii = jnp.minimum(idx, bs.capacity - 1)
    r = bs.rows[ii]
    c = bs.cols[ii]
    v = bs.vals[ii]
    ok = ok & (r < bs.nrows)
    flat = jnp.where(ok, r * pwin + (c - h * block_cols), pk * pwin)
    comb = scatter_combine_for(sr)
    dense = jnp.full((pk * pwin,), sr.zero(bs.vals.dtype), bs.vals.dtype)
    dense = getattr(dense.at[flat], comb)(v, mode="drop")
    return dense.reshape(pk, pwin)


def _window_stage_product(
    sr: Semiring, kind: str, da, panel, mode: str, interpret: bool,
):
    """One stage's dense window product on the matrix unit."""
    from ..ops.pallas_kernels import semiring_matmul

    if kind == "plus_times":
        return _mxu_dot(da, panel, mode, da.dtype)
    return semiring_matmul(
        kind, da, panel, bm=256, bk=512, bn=256, interpret=interpret
    )


def _windowed_dims(backend: str, block_cols, lrB: int, lcB: int):
    """Static padded dims of the windowed accumulate: (two_d, pcols, pk,
    pwin)."""
    two_d = backend == "dot" and block_cols is not None
    if backend == "dot":
        pcols = _pad128(lcB)
        pk = _pad128(lrB)
        pwin = _pad128(block_cols) if two_d else None
    else:
        pcols = -(-lcB // 128) * 128
        pk = pwin = None
    return two_d, pcols, pk, pwin


def _windowed_stage_b_side(sr, b_stage, backend, two_d, pk, pcols,
                           block_cols):
    """Per-stage B-side preprocessing: CSR (scatter), dense tile (1D
    dot), or (col-major sorted tile, window slot starts) (2D dot)."""
    from ..ops.spgemm import densify_combine

    if backend == "scatter":
        return CSR.from_tuples(b_stage)
    if not two_d:
        return densify_combine(sr, b_stage, pk, pcols)
    return _colmajor_with_starts(b_stage, block_cols)


def _dot_block_windows(
    sr: Semiring, a_stages, b_sides, lo, *, rb, hs, out_caps_row,
    block_cols, pk, pwin, panel_cap, mode, interpret, lrA, lcB, zero,
):
    """One ROW BLOCK of the 2D ``dot`` tier over gathered stage tiles:
    every stage's dense product added into each live col window's
    accumulator, each window extracted once.  Returns (the windows'
    chunks in ``hs`` order, worst overflow)."""
    from ..ops.spgemm import densify_combine, mask_rows

    kind = _PALLAS_KINDS.get(sr.name)
    arows = _pad128(rb)
    accs = {}
    for a_stage, (bs_sorted, b_starts) in zip(a_stages, b_sides):
        with jax.named_scope("sq.densify"):
            am = mask_rows(a_stage, lo, lo + rb)
            da = densify_combine(
                sr, _shift_rowblock(am, lo, arows), arows, pk
            )
        for h in hs:
            with jax.named_scope("sq.densify"):
                panel = _dense_col_panel(
                    sr, bs_sorted, b_starts, h, block_cols, pk, pwin,
                    panel_cap,
                )
            with jax.named_scope("sq.dot"):
                prod = _window_stage_product(
                    sr, kind, da, panel, mode, interpret
                )
                # the first stage's product IS the accumulator: no
                # window-sized fill and add of the semiring's zero
                accs[h] = prod if h not in accs else sr.add(accs[h], prod)
    chunks = []
    worst = jnp.int32(0)
    for h in hs:
        with jax.named_scope("sq.extract"):
            chunk, over = _extract_window_2d(
                accs[h], zero, lo, h, rb, block_cols, lrA, lcB,
                out_caps_row[h],
            )
        worst = jnp.maximum(worst, over)
        chunks.append(chunk)
    return chunks, worst


def _windowed_gathered_compute(
    sr: Semiring, a_stages, b_stages, *, lrA, lrB, lcB, block_rows,
    flop_caps, out_caps, skip, backend, mode, chunk_w, interpret,
    block_cols, panel_cap, zero, dtype,
):
    """Block-outer windowed accumulate + extract over PRE-GATHERED stage
    tiles — the per-device core of the gathered schedule, shared by the
    2D shard_map kernel and the per-layer 3D kernel
    (``mesh3d.summa3d_spgemm_windowed``).  Iterates the PACKED launch
    list (``_live_windows_by_block`` / ``packed_windows``) so sparse
    plans pay one accumulate+extract per occupied window.  Returns
    (chunks, worst)."""
    from ..ops.spgemm import (
        accumulate_block_scatter,
        densify_combine,
        mask_rows,
    )

    p = len(a_stages)
    kind = _PALLAS_KINDS.get(sr.name)
    two_d, pcols, pk, pwin = _windowed_dims(backend, block_cols, lrB, lcB)
    b_sides = [
        _windowed_stage_b_side(sr, b, backend, two_d, pk, pcols, block_cols)
        for b in b_stages
    ]
    chunks = []
    worst = jnp.int32(0)
    if two_d:
        for g, hs in _live_windows_by_block(skip):
            lo = g * block_rows
            rb = min(block_rows, lrA - lo)
            chunks_g, over = _dot_block_windows(
                sr, a_stages, b_sides, lo, rb=rb, hs=hs,
                out_caps_row=out_caps[g], block_cols=block_cols, pk=pk,
                pwin=pwin, panel_cap=panel_cap, mode=mode,
                interpret=interpret, lrA=lrA, lcB=lcB, zero=zero,
            )
            worst = jnp.maximum(worst, over)
            chunks += chunks_g
        return chunks, worst
    for g in packed_windows(skip):
        lo = g * block_rows
        rb = min(block_rows, lrA - lo)
        arows = _pad128(rb) if backend == "dot" else rb
        acc = jnp.full((arows, pcols), zero, dtype)
        for s in range(p):
            am = mask_rows(a_stages[s], lo, lo + rb)
            if backend == "scatter":
                acc = accumulate_block_scatter(
                    sr, acc, am, b_sides[s], row_lo=lo,
                    flop_capacity=max(flop_caps[g], chunk_w),
                    chunk_w=chunk_w,
                )
            else:
                da = densify_combine(
                    sr, _shift_rowblock(am, lo, arows), arows, pk
                )
                acc = sr.add(
                    acc,
                    _window_stage_product(
                        sr, kind, da, b_sides[s], mode, interpret
                    ),
                )
        chunk, over = _extract_block_1d(
            acc, zero, lo, rb, lrA, lcB, out_caps[g]
        )
        worst = jnp.maximum(worst, over)
        chunks.append(chunk)
    return chunks, worst


def _windowed_carousel_compute(
    sr: Semiring, a_mine, b_mine, *, p, lrA, lrB, lcB, block_rows,
    flop_caps, out_caps, skip, backend, mode, chunk_w, interpret,
    block_cols, panel_cap, zero, dtype, pipeline,
):
    """STAGE-OUTER carousel windowed accumulate + extract: the operands
    live in two-slot neighbor-rotation buffers (O(2·tile) sparse memory
    instead of the gathered schedule's O(p·tile)) and with
    ``pipeline=True`` stage ``s+1``'s ``ppermute`` is issued BEFORE
    stage ``s``'s tiles are consumed, so the ICI rotation overlaps the
    MXU/scatter accumulate.  The trade: ALL live block/window
    accumulators coexist across the stage loop (the gathered schedule
    keeps one block live at a time) — callers pick this schedule where
    the per-device dense tile is grid-divided small (the distributed
    mid-scale regime it is built for).

    ``pipeline=False`` is the measurement control: the rotation is
    pinned BEHIND the stage's accumulate (``_chain_tiles``), the strict
    rotate→compute→rotate serial chain."""
    from ..ops.spgemm import (
        accumulate_block_scatter,
        densify_combine,
        mask_rows,
    )

    kind = _PALLAS_KINDS.get(sr.name)
    two_d, pcols, pk, pwin = _windowed_dims(backend, block_cols, lrB, lcB)

    def block_geom(g):
        lo = g * block_rows
        rb = min(block_rows, lrA - lo)
        arows = _pad128(rb) if backend == "dot" else rb
        return lo, rb, arows

    if two_d:
        live = _live_windows_by_block(skip)
        accs = {
            (g, h): jnp.full((block_geom(g)[2], pwin), zero, dtype)
            for g, hs in live for h in hs
        }
    else:
        live = packed_windows(skip)
        accs = {
            g: jnp.full((block_geom(g)[2], pcols), zero, dtype)
            for g in live
        }
    skew_a, skew_b, rot_a, rot_b = _carousel_perms(p)
    a_cur = _rotate_tiles(a_mine, skew_a)
    b_cur = _rotate_tiles(b_mine, skew_b)
    for s in range(p):
        a_nxt = b_nxt = None
        overlapped = pipeline and s != p - 1
        if overlapped:
            a_nxt = _rotate_tiles(a_cur, rot_a)
            b_nxt = _rotate_tiles(b_cur, rot_b)
        if obs.ENABLED:
            # trace-time schedule record: one event per carousel stage
            # noting whether its successor rotation was issued early
            obs.span_event(
                "spgemm.pipeline.stage", stage=s,
                overlapped=bool(overlapped),
            )
        b_side = _windowed_stage_b_side(
            sr, b_cur, backend, two_d, pk, pcols, block_cols
        )
        if two_d:
            bs_sorted, b_starts = b_side
            for g, hs in live:
                lo, rb, arows = block_geom(g)
                with jax.named_scope("sq.densify"):
                    am = mask_rows(a_cur, lo, lo + rb)
                    da = densify_combine(
                        sr, _shift_rowblock(am, lo, arows), arows, pk
                    )
                for h in hs:
                    with jax.named_scope("sq.densify"):
                        panel = _dense_col_panel(
                            sr, bs_sorted, b_starts, h, block_cols, pk,
                            pwin, panel_cap,
                        )
                    with jax.named_scope("sq.dot"):
                        accs[(g, h)] = sr.add(
                            accs[(g, h)],
                            _window_stage_product(
                                sr, kind, da, panel, mode, interpret
                            ),
                        )
        else:
            for g in live:
                lo, rb, arows = block_geom(g)
                am = mask_rows(a_cur, lo, lo + rb)
                if backend == "scatter":
                    accs[g] = accumulate_block_scatter(
                        sr, accs[g], am, b_side, row_lo=lo,
                        flop_capacity=max(flop_caps[g], chunk_w),
                        chunk_w=chunk_w,
                    )
                else:
                    da = densify_combine(
                        sr, _shift_rowblock(am, lo, arows), arows, pk
                    )
                    accs[g] = sr.add(
                        accs[g],
                        _window_stage_product(
                            sr, kind, da, b_side, mode, interpret
                        ),
                    )
        if s != p - 1:
            if not pipeline:
                # serial-chain control: rotation waits for this stage's
                # ENTIRE accumulate — every live accumulator, else XLA
                # may overlap the rotation with the unpinned blocks and
                # the control stops being serial
                dep = (
                    tuple(accs.values()) if accs else jnp.int32(0)
                )
                a_cur = _chain_tiles(a_cur, dep)
                b_cur = _chain_tiles(b_cur, dep)
                a_nxt = _rotate_tiles(a_cur, rot_a)
                b_nxt = _rotate_tiles(b_cur, rot_b)
            a_cur, b_cur = a_nxt, b_nxt
    chunks = []
    worst = jnp.int32(0)
    if two_d:
        for g, hs in live:
            lo, rb, _ = block_geom(g)
            for h in hs:
                with jax.named_scope("sq.extract"):
                    chunk, over = _extract_window_2d(
                        accs[(g, h)], zero, lo, h, rb, block_cols, lrA,
                        lcB, out_caps[g][h],
                    )
                worst = jnp.maximum(worst, over)
                chunks.append(chunk)
        return chunks, worst
    for g in live:
        lo, rb, _ = block_geom(g)
        chunk, over = _extract_block_1d(
            accs[g], zero, lo, rb, lrA, lcB, out_caps[g]
        )
        worst = jnp.maximum(worst, over)
        chunks.append(chunk)
    return chunks, worst


@partial(
    jax.jit,
    static_argnames=(
        "sr", "block_rows", "flop_caps", "out_caps", "skip", "backend",
        "mode", "chunk_w", "interpret", "block_cols", "panel_cap",
        "ring", "pipeline",
    ),
)
def summa_spgemm_windowed(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    block_rows: int,
    flop_caps: tuple,
    out_caps: tuple,
    skip: tuple | None = None,
    backend: str = "scatter",
    mode: str = "f32",
    chunk_w: int = 8,
    interpret: bool = False,
    block_cols: int | None = None,
    panel_cap: int | None = None,
    ring: bool = False,
    pipeline: bool = True,
) -> tuple[SpParMat, jax.Array]:
    """Sort-free SUMMA over dense ROW-BLOCK accumulators — the mid-scale
    general sparse-output tier.

    The classic ESC kernel's cost wall is the (row, col) sort over every
    expansion slot (~87 s at scale 16 on the chip; minutes on XLA:CPU,
    whose sort runs ~1 M slots/s).  Here each output row block is
    accumulated DENSELY and extracted once:

      per row block g (static python loop, empty blocks SKIPPED via the
      symbolic skip list):
        acc[g]  <- semiring-fold of every stage's expansion restricted
                   to the block's rows
            backend="scatter": chunked expansion + one native
                ``at[].{add,min,max}`` per stage (ops/spgemm.
                accumulate_block_scatter) — the general path on backends
                with a scatter unit (XLA:CPU);
            backend="dot": densified stage operands × `_mxu_dot` /
                the Pallas semiring matmul — the MXU path
                (``summa_spgemm_mxu`` generalized to row blocks).  With
                ``block_cols=None`` the dense B stage operand spans the
                whole tile width (legacy 1D form — only fits inside the
                mxu envelope); with ``block_cols`` set the output is
                tiled into (row block × col window) 2D windows and each
                stage densifies only B's COLUMN PANEL for the current
                window (CSC slot-range slice → [pk, pwin] dense panel,
                ``_dense_col_panel``), so peak stage-operand memory is
                pk × pwin cells — bounded by the window, which is what
                makes this the TPU mid-scale tier.  Both dot forms
                densify with the semiring's combining scatter
                (``densify_combine``), so duplicate-entry COO inputs
                are absorbed exactly on EVERY windowed backend; only
                the mxu tier keeps the unique-entries precondition.
        extract acc with the windowed output-driven extraction
        (``sparsify_windowed``), sized by the exact symbolic
        per-block (or per-window) output bound (``windowed_plan`` /
        ``windowed_plan_2d``); symbolically-empty 2D windows are never
        densified, matmul'd, or scanned.

    In 2D form ``flop_caps``/``out_caps``/``skip`` are tuples of
    per-block tuples from ``windowed_plan_2d`` and ``panel_cap`` bounds
    one window's B-panel nnz (``summa_window_bnnz``).  Returns
    (C, overflow) with the same overflow contract as
    ``summa_spgemm_mxu`` — though with symbolic-bound out_caps overflow
    is structurally zero (the bound dominates the realized nnz).

    The output tile's valid slots form a compacted PREFIX PER BLOCK
    (1D: globally row-ordered; 2D: row-block-major, then window-major
    within a block — NOT globally row-sorted), with padding interleaved
    between blocks — ``valid_mask`` semantics, which every downstream
    consumer (to_dense, CSR/CSC builds, ewise, redistribute) honors;
    a global re-sort would reintroduce the cost this kernel removes.

    SCHEDULES.  ``ring=False`` (default) is the GATHERED schedule: one
    fused all_gather per operand stages all tiles up front, then a
    block-outer loop keeps one dense accumulator live at a time (peak
    sparse memory O(p·tile)).  ``ring=True`` is the STAGE-PIPELINED
    CAROUSEL: operands rotate neighbor-to-neighbor in two-slot buffers
    (peak sparse memory O(2·tile)) and with ``pipeline=True`` stage
    s+1's ``ppermute`` is issued before stage s's tiles are consumed,
    so the ICI rotation overlaps the accumulate — the van de Geijn &
    Watts overlap the gathered schedule leaves to chance.  The carousel
    keeps every live block/window accumulator alive across the stage
    loop, so it fits where per-device tiles are grid-divided small (its
    distributed target regime).  ``pipeline=False`` pins the strict
    rotate→compute→rotate serial chain (the measurement control).
    Both schedules iterate the PACKED launch list (``packed_windows`` /
    ``packed_windows_2d``) and emit identical chunk layouts.
    """
    from ..ops.spgemm import scatter_combine_for

    _check_compat(A, B)
    grid = A.grid
    p = grid.pr
    lrA, lcA = A.local_rows, A.local_cols
    lrB, lcB = B.local_rows, B.local_cols
    nblocks = -(-lrA // block_rows)
    two_d = backend == "dot" and block_cols is not None
    ncw = -(-lcB // block_cols) if two_d else 1
    if skip is None:
        skip = ((False,) * ncw,) * nblocks if two_d else (False,) * nblocks
    assert len(flop_caps) == len(out_caps) == len(skip) == nblocks, (
        nblocks, len(flop_caps), len(out_caps), len(skip)
    )
    kind = _PALLAS_KINDS.get(sr.name)
    if backend == "dot":
        assert kind is not None, (
            f"backend='dot' supports semirings {sorted(_PALLAS_KINDS)}; "
            f"got {sr.name}"
        )
        assert scatter_combine_for(sr) is not None, sr.name
        if two_d:
            assert panel_cap is not None and panel_cap >= 1
            assert all(len(row) == ncw for row in skip), (ncw, skip)
    else:
        assert backend == "scatter", backend
        assert scatter_combine_for(sr) is not None, (
            f"semiring {sr.name} has no scatter combiner; use the ESC "
            "path"
        )
    if obs.ENABLED:
        obs.count(
            "trace.summa_spgemm_windowed",
            backend=("dot2d" if two_d else backend),
            ring=ring,
        )
        if ring and pipeline and p > 1:
            # trace-time: carousel stages whose successor rotation is
            # issued early (overlappable) in this compiled program
            obs.count("spgemm.pipeline.stages_overlapped", p - 1)
    zero = float(np.asarray(sr.zero_fn(A.vals.dtype)))
    static = dict(
        lrA=lrA, lrB=lrB, lcB=lcB, block_rows=block_rows,
        flop_caps=flop_caps, out_caps=out_caps, skip=skip,
        backend=backend, mode=mode, chunk_w=chunk_w,
        interpret=interpret, block_cols=block_cols if two_d else None,
        panel_cap=panel_cap, zero=zero, dtype=A.vals.dtype,
    )

    def body(ar, ac, av, an, br, bc, bv, bn):
        a_mine = A.local_tile(ar, ac, av, an)
        b_mine = B.local_tile(br, bc, bv, bn)
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
        if not chunks:  # every block skipped: structurally empty output
            chunks.append(SpTuples.empty(lrA, lcB, 1, A.vals.dtype))
        out = SpTuples.concat(chunks)
        worst = lax.pmax(lax.pmax(worst, ROW_AXIS), COL_AXIS)
        return SpParMat._pack_tile(out) + (worst[None, None],)

    r, c, v, n, overflow = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 8,
        out_specs=(TILE_SPEC,) * 5,
        check_vma=False,
    )(A.rows, A.cols, A.vals, A.nnz, B.rows, B.cols, B.vals, B.nnz)
    mat = SpParMat(
        rows=r, cols=c, vals=v, nnz=n,
        nrows=A.nrows, ncols=B.ncols, grid=grid,
    )
    return mat, overflow[0, 0]


class PhaseAdjustedWarning(UserWarning):
    """Structured phase-adaptation notice (VERDICT r3 weak #8): carries
    (requested, actual, local_cols) so a memory-budget caller can catch it
    programmatically (``warnings.catch_warnings(record=True)``) instead of
    parsing the message.  ``actual`` is always >= ``requested`` (phases
    only grow, so each phase stays within the budgeted size) and <= 4x."""

    def __init__(self, requested: int, actual: int, local_cols: int):
        self.requested = requested
        self.actual = actual
        self.local_cols = local_cols
        super().__init__(
            f"mem_efficient_spgemm: {requested} phases does not divide "
            f"local_cols={local_cols}; using the nearest divisor {actual} "
            "instead"
        )


def mem_efficient_spgemm(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    phases: int,
    *,
    slack: float = 1.05,
    prune_fn=None,
    scan: bool = False,
) -> SpParMat:
    """Phased SUMMA: C = A ⊗ B computed over column chunks of B.

    Reference: ``MemEfficientSpGEMM`` (ParFriends.h:450-731) — B is
    ``ColSplit`` into ``phases`` local column chunks; each phase runs a full
    SUMMA plus an optional ``prune_fn`` hook (MCL's prune/recover/select,
    ParFriends.h:186-350), and phase outputs concatenate back. Peak expansion
    memory drops ~``phases``-fold at the cost of re-gathering A every phase.
    The reference auto-computes ``phases`` from a memory budget via
    ``EstPerProcessNnzSUMMA``; here the symbolic pass inside ``spgemm`` sizes
    each phase exactly, so callers choose ``phases`` directly.

    ``scan=True`` additionally bounds each phase's EXPANSION memory by the
    output (``spgemm_scan``'s running accumulator) — phases cap the gather
    width, scan caps the ESC working set; together they give the
    O(output)-memory profile of the reference's hash path.
    """
    lc = B.local_cols
    if phases > 1 and B.ncols != lc * B.grid.pc:
        # An irregular (padded) column distribution cannot be phase-split;
        # silently unphasing would blow the caller's memory budget, so fail
        # loudly with guidance (reference phase contract: ParFriends.h:450).
        raise ValueError(
            f"mem_efficient_spgemm: ncols={B.ncols} is not evenly "
            f"distributed over pc={B.grid.pc} (local_cols={lc}); pad the "
            "matrix to a multiple of pc or run with phases=1"
        )
    if phases > 1 and lc % phases:
        # Nearest divisor >= requested keeps every phase AT MOST the size
        # the caller budgeted for (more phases = smaller phases = safe) —
        # but only within 4x, so a divisor-poor lc (e.g. prime) fails
        # loudly instead of silently multiplying the SUMMA pass count.
        adj = min(phases, lc)
        while adj <= lc and lc % adj:
            adj += 1
        if adj > 4 * phases:
            raise ValueError(
                f"mem_efficient_spgemm: {phases} phases does not divide "
                f"local_cols={lc} and the nearest divisor above it ({adj}) "
                "is >4x the request; choose a phase count dividing "
                f"local_cols (divisors of {lc}) or repad the matrix"
            )
        import warnings

        warnings.warn(
            PhaseAdjustedWarning(phases, adj, lc), stacklevel=2,
        )
        if obs.ENABLED:
            obs.count("spgemm.phase_adjusted")
        phases = adj
    if obs.ENABLED:
        # after adjustment: the phase count actually executed, matching
        # the number of spgemm.phase spans below
        obs.gauge("spgemm.phases", phases, scan=str(scan))
    mult = (
        (lambda a, b: spgemm_scan(sr, a, b, slack=slack))
        if scan
        else (lambda a, b: spgemm(sr, a, b, slack))
    )
    if phases <= 1:
        C = mult(A, B)
        return prune_fn(C) if prune_fn is not None else C
    outs = []
    for pi, Bs in enumerate(B.col_split(phases)):
        # A phase holds ~1/phases of the nnz but inherits B's full slot
        # capacity from col_split; truncate so the per-phase SUMMA gathers
        # phase-sized arrays (the point of phasing is peak-memory reduction).
        with obs.span("spgemm.phase", phase=pi):
            C = mult(A, Bs.shrink_to_fit())
            if prune_fn is not None:
                C = prune_fn(C)
        outs.append(C)
    return SpParMat.col_concatenate(outs)


def block_spgemm(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    row_blocks: int = 1,
    col_blocks: int = 1,
    slack: float = 1.05,
):
    """Generator over output blocks: yields ((i, j), C_ij) where
    C_ij = A[rowblock_i, :] ⊗ B[:, colblock_j].

    Reference: ``BlockSpGEMM`` (BlockSpGEMM.h:16-137) — iterate SUMMA over
    logical output blocks so no more than one block's expansion is live at
    a time (out-of-core-style memory bounding; the driver streams blocks to
    the caller, e.g. for writeout). Splits are LOCAL like col_split;
    ``SpParMat.col_concatenate`` / stacking reassembles if needed.
    """
    a_rows = A.row_split(row_blocks) if row_blocks > 1 else [A]
    b_cols = B.col_split(col_blocks) if col_blocks > 1 else [B]
    b_cols = [b.shrink_to_fit() for b in b_cols]  # once, not per row block
    for i, Ai in enumerate(a_rows):
        Ai = Ai.shrink_to_fit()
        for j, Bj in enumerate(b_cols):
            yield (i, j), spgemm(sr, Ai, Bj, slack)


def estimate_flops(A: SpParMat, B: SpParMat) -> int:
    """Total semiring multiplications of A ⊗ B.

    Reference: ``EstimateFLOP`` (ParFriends.h:356-448) — here the exact
    distributed symbolic pass summed over stages and tiles (true scalar
    multiplies, not chunk-padded slots).
    """
    import numpy as np

    return int(
        host_value(summa_stage_flops(A, B, padded=False)).astype(np.float64).sum()
    )


def calculate_phases(
    A: SpParMat, B: SpParMat, per_device_memory_bytes: int,
    slack: float = 1.05,
) -> int:
    """Phase count for ``mem_efficient_spgemm`` from a memory budget.

    Reference: ``CalculateNumberOfPhases`` (ParFriends.h:733-797) — there
    from ``perProcessMemory`` GB and the SUMMA nnz estimate; here from the
    peak per-device expansion of the unphased product (stage flops × slot
    bytes) against the caller's budget, rounded to a divisor-friendly
    power of two.
    """
    per_stage = host_value(summa_stage_flops(A, B)).astype(np.float64)
    slot_bytes = 4 + 4 + np.dtype(A.dtype).itemsize  # row + col + value
    # Peak per-device expansion follows the ALLOCATED shapes, not the valid
    # entries: summa_spgemm pads every one of the p coexisting stage chunks
    # to flop_capacity = max stage flops (static shapes), so the worst-case
    # skew allocates p x the single-stage max.
    p = A.grid.pr
    peak = per_stage.max() * p * slot_bytes * slack
    phases = max(1, int(np.ceil(peak / max(per_device_memory_bytes, 1))))
    phases = 1 << (phases - 1).bit_length()
    lc = B.local_cols
    if B.ncols != lc * B.grid.pc:
        # Irregular (padded) column distribution cannot be phase-split —
        # mem_efficient_spgemm rejects phases>1 there, so don't request it.
        return 1
    # Clamp to a divisor of B's local column count — mem_efficient_spgemm
    # only accepts divisors (it adjusts upward within 4x, errors beyond).
    phases = min(phases, max(lc, 1))
    while phases > 1 and lc % phases:
        phases >>= 1
    return phases


def estimate_nnz_upper(A: SpParMat, B: SpParMat) -> int:
    """Upper bound on nnz(C): per-tile flops clamped by the dense tile.

    The role of ``EstPerProcessNnzSUMMA``'s estimate (ParFriends.h:1243);
    exact nnz would need the hash symbolic pass — for capacity sizing the
    clamped-flops bound is what ``summa_capacities`` already uses.
    """
    import numpy as np

    # padded=False: size from TRUE flops (like estimate_flops) — the
    # chunk-padded counts belong to expansion capacities only, and at
    # CHUNK_W=32 they can inflate this bound 32x for short-B-row matrices
    # (ADVICE r3)
    per_stage = host_value(
        summa_stage_flops(A, B, padded=False)
    ).astype(np.float64)
    per_tile = per_stage.sum(axis=0)
    dense_tile = A.local_rows * B.local_cols
    return int(np.minimum(per_tile, dense_tile).sum())


def spgemm(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    slack: float = 1.05,
    *,
    pow2_caps: bool = True,
    merge: str | None = None,
) -> SpParMat:
    """Convenience: symbolic pass → sized numeric SUMMA (unjitted entry).

    ≈ the user-facing ``Mult_AnXBn_Synch`` call; inside jit loops use
    ``summa_spgemm`` with pre-chosen capacities instead.

    ``pow2_caps`` rounds both capacities up to powers of two (≤2× memory
    slack) so iterative callers (MCL's expand loop, BC's per-level products)
    hit the XLA compilation cache instead of recompiling for every new nnz.

    ``merge``: the ESC stage-chunk combine (sort | runs) — ``None`` is
    ``"sort"`` (the classic path).  ``"hash"`` is a 3D-fiber tier; here
    it degrades to ``"runs"`` (the expansion-sized chunks would swamp
    an out-capacity table).
    """
    merge_source = "heuristic" if merge is None else "arg"
    if merge is None:
        merge = "sort"
    elif merge == "hash":
        merge = "runs"
    with obs.span("spgemm", sr=sr.name):
        if obs.ENABLED:
            obs.count(
                "spgemm.merge.tier", tier=merge, source=merge_source,
                op="spgemm",
            )
        flop_cap, out_cap = summa_capacities(A, B, slack)
        if pow2_caps:
            dense_tile = A.local_rows * B.local_cols
            flop_cap = 1 << (flop_cap - 1).bit_length()
            out_cap = min(1 << (out_cap - 1).bit_length(), max(dense_tile, 1))
        if obs.ENABLED:
            obs.span_event(
                "capacities", flop_capacity=flop_cap, out_capacity=out_cap
            )
        C = summa_spgemm(
            sr, A, B, flop_capacity=flop_cap, out_capacity=out_cap,
            merge=merge,
        )
        _record_realized_nnz(C)
        return C


def _record_realized_nnz(C: SpParMat) -> None:
    """Realized output fill-in (the other half of symbolic-vs-realized).
    Reading ``C.nnz`` is a device readback, so this records ONLY under
    the explicit ``obs.DEVICE_SYNC`` opt-in — never in a timed section."""
    if obs.ENABLED and obs.DEVICE_SYNC:
        realized = int(np.asarray(host_value(C.nnz)).sum())
        obs.count("spgemm.realized_nnz", realized)


@partial(
    jax.jit,
    static_argnames=("sr", "flop_capacity", "out_capacity", "ring"),
)
def summa_spgemm_scan(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    flop_capacity: int,
    out_capacity: int,
    ring: bool = False,
) -> tuple[SpParMat, jax.Array]:
    """Output-bounded SUMMA: stage expansions fold into a RUNNING
    accumulator instead of coexisting.

    ``summa_spgemm`` keeps all p stage chunks live (peak ≈ p·flop_capacity
    slots — memory scales with FLOPs, the round-1 weakness); here each
    stage's expansion is immediately merged into an out_capacity-slot
    accumulator, so peak ≈ flop_capacity + 2·out_capacity slots — memory
    scales with the OUTPUT, the property the reference gets from hash
    accumulation (``LocalHybridSpGEMM``'s O(nnz_out) working set,
    mtSpGEMM.h:214-440). The trade is p small sorts instead of one big one.

    Returns (C, overflow): ``overflow`` is the global max, over tiles and
    stages, of (observed distinct keys − out_capacity). Zero means C is
    exact. Positive means truncation happened; note that once a stage
    truncates, its dropped keys vanish from later stages' counts, so a
    positive ``overflow`` is a LOWER BOUND on the true shortfall — always
    a correct truncation signal, not an exact requirement.
    ``spgemm_scan`` therefore grows capacity geometrically per retry
    rather than trusting one measurement (the estimateNNZ_Hash role,
    realized iteratively).
    """
    _check_compat(A, B)
    grid = A.grid
    p = grid.pr
    if obs.ENABLED:
        obs.count("trace.summa_spgemm_scan", ring=ring)
        if ring and p > 1:
            obs.count("spgemm.pipeline.stages_overlapped", p - 1)

    def body(ar, ac, av, an, br, bc, bv, bn):
        a_mine = A.local_tile(ar, ac, av, an)
        b_mine = B.local_tile(br, bc, bv, bn)
        acc = SpTuples.empty(
            a_mine.nrows, b_mine.ncols, out_capacity, A.vals.dtype
        )
        worst = jnp.int32(0)

        def merge(acc, worst, a_stage, b_stage):
            with jax.named_scope("sq.densify"):
                b_csr = CSR.from_tuples(b_stage)
            with jax.named_scope("sq.dot"):
                chunk = esc_expand(sr, a_stage, b_csr, flop_capacity)
            with jax.named_scope("sq.extract"):
                merged = SpTuples.concat([acc, chunk])
                acc, distinct = merged.compact_counted(
                    sr, capacity=out_capacity)
            return acc, jnp.maximum(worst, distinct - out_capacity)

        if not ring:
            a_stages = _gather_stage_tiles(a_mine, COL_AXIS, p)
            b_stages = _gather_stage_tiles(b_mine, ROW_AXIS, p)
            for s in range(p):
                acc, worst = merge(acc, worst, a_stages[s], b_stages[s])
        else:
            # stage-pipelined carousel (shared two-slot schedule; see
            # summa_spgemm's ring path): stage s+1's rotation is issued
            # before stage s's expand+merge consumes the current tiles
            for s, a_cur, b_cur in _carousel_stages(a_mine, b_mine, p):
                acc, worst = merge(acc, worst, a_cur, b_cur)

        worst = lax.pmax(lax.pmax(worst, ROW_AXIS), COL_AXIS)
        return SpParMat._pack_tile(acc) + (worst[None, None],)

    r, c, v, n, overflow = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 8,
        out_specs=(TILE_SPEC,) * 4 + (TILE_SPEC,),
        check_vma=False,
    )(A.rows, A.cols, A.vals, A.nnz, B.rows, B.cols, B.vals, B.nnz)
    mat = SpParMat(
        rows=r, cols=c, vals=v, nnz=n,
        nrows=A.nrows, ncols=B.ncols, grid=grid,
    )
    return mat, overflow[0, 0]


def spgemm_scan(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    out_capacity: int | None = None,
    slack: float = 1.1,
    max_retries: int = 3,
    ring: bool = False,
) -> SpParMat:
    """Output-bounded SpGEMM entry: size, run, retry on overflow.

    The initial ``out_capacity`` guess is deliberately cheap (a fraction of
    the clamped-flops bound); the first attempt's EXACT distinct-key count
    then corrects it, so high-collision products (MCL's A²) never allocate
    flops-shaped outputs. One host sync per attempt (off the hot path; a
    caller-provided ``out_capacity`` avoids it).
    """
    with obs.span("spgemm.scan", sr=sr.name):
        flop_cap, flops_out_cap = summa_capacities(A, B, slack)
        if out_capacity is None:
            # optimistic: half the flops bound, floor at the input sizes
            out_capacity = max(
                min(flops_out_cap, max(A.capacity, B.capacity)), 64
            )
        out_capacity = 1 << (int(out_capacity) - 1).bit_length()
        for attempt in range(max_retries + 1):
            C, overflow = summa_spgemm_scan(
                sr, A, B, flop_capacity=flop_cap, out_capacity=out_capacity,
                ring=ring,
            )
            over = int(overflow)
            if over <= 0:
                if obs.ENABLED:
                    obs.count("spgemm.scan.overflow_retries", attempt)
                    obs.span_event(
                        "sized", flop_capacity=flop_cap,
                        out_capacity=out_capacity, retries=attempt,
                    )
                    _record_realized_nnz(C)
                return C
            if obs.ENABLED:
                obs.count("spgemm.scan.overflow_slots", over)
            # ``over`` under-reports when an early stage truncated (see
            # summa_spgemm_scan docstring) — grow geometrically, at least 2x
            out_capacity = max(
                1 << (out_capacity + over - 1).bit_length(), out_capacity * 2
            )
        raise ValueError(
            f"spgemm_scan still overflowing by {over} after {max_retries} "
            "retries; pass an explicit out_capacity"
        )


def _pad128(x: int, to: int = 512) -> int:
    """Pad to a Pallas/MXU-friendly multiple (512 covers the tropical
    kernel's block sizes; plus_times only needs 128 but the extra padding
    is noise at these sizes)."""
    return -(-x // to) * to


_PALLAS_KINDS = {
    "plus_times": "plus_times",
    "min_plus": "min_plus",
    "max_min": "max_min",
}


def _split_bf16(x):
    """A float32 array as two bfloat16 halves, ``hi + lo`` within 2^-17
    of it: ``hi`` the operand rounded to bfloat16's eight exponent and
    seven fraction bits, ``lo`` what that left, rounded likewise.  The
    rounding is ``lax.reduce_precision``, which a compiler may not drop
    as it may a cast's round trip (``_mxu_dot``)."""
    hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _mxu_dot(da, db, mode: str, out_dtype):
    """Dense plus_times stage product at the requested precision.

    Measured on the v5e (one [4096, 16384] x [16384, 8192] product of
    f32 operands, 1.1e12 flop, best of three; my chip run, PR 40):
      f32 native dot      7.00 ms, 157 TFLOP/s: the DEFAULT precision,
                          which on this chip is one bf16 pass with an
                          f32 accumulator, so NOT exact f32 (the same
                          time as the next line, to the microsecond);
                          ``Precision.HIGHEST`` measured 36.8 ms, 29.9
                          TFLOP/s, and no mode here asks for it
      bf16 inputs         7.02 ms, 157 TFLOP/s (EXACT when inputs are
                          bf16-representable — e.g. 0/1 adjacency — and
                          the f32-accumulated counts stay < 2^24); 6.61
                          ms, 166 TFLOP/s on operands already bf16
      bf16x3 split-float  24.3 ms, 45 TFLOP/s (hi/lo decomposition,
                          error ~2^-16 per operand — f32-grade for graph
                          work)

    The split's hi half is ``lax.reduce_precision`` (``_split_bf16``),
    not ``x.astype(bfloat16)``: written as ``x - x.astype(bfloat16)
    .astype(float32)`` the chip's compiler keeps the round trip in
    float32 inside its fusion, the lo half is 0 and three passes carry
    one pass's error (my chip run, PR 44, a [1024, 4096] x [4096, 1024]
    product of uniform values against float64, largest error over the
    largest entry: the cast 1.97e-4, which is the one-pass modes' to the
    digit; ``reduce_precision`` 5.7e-7; a mask on the top 16 bits, which
    truncates, 7.4e-6).  A CPU keeps the cast's rounding, so no tier-1
    case saw it.
    """
    if mode == "f32":
        return jnp.dot(da, db, preferred_element_type=out_dtype)
    if mode == "bf16":
        return jnp.dot(
            da.astype(jnp.bfloat16), db.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ).astype(out_dtype)
    assert mode == "bf16x3", mode
    (ah, al), (bh, bl) = _split_bf16(da), _split_bf16(db)
    out = (
        jnp.dot(ah, bh, preferred_element_type=jnp.float32)
        + jnp.dot(ah, bl, preferred_element_type=jnp.float32)
        + jnp.dot(al, bh, preferred_element_type=jnp.float32)
    )
    return out.astype(out_dtype)


@partial(
    jax.jit,
    static_argnames=("sr", "out_capacity", "mode", "interpret"),
)
def summa_spgemm_mxu(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    out_capacity: int,
    mode: str = "f32",
    interpret: bool = False,
) -> tuple[SpParMat, jax.Array]:
    """Dense-block SUMMA: stage products run on the MATRIX UNIT.

    On this TPU every sparse-side primitive pays per element (a
    scatter-add of 40.7 M int32 into 16,384 bins measured 273 ms, 149 M
    adds/s; my chip run, PR 40) while the MXU delivers 157 TFLOP/s on
    bf16 blocks (``_mxu_dot``) — below ~32K tile dims, spending n³ dense
    FLOPs beats sorting the sparse expansion outright: stage tiles densify
    (sorted-scatter), multiply via ``_mxu_dot`` (plus_times; ``mode``
    picks the precision/speed point) or the Pallas semiring matmul
    (min_plus/max_min — XLA has no tropical MXU lowering), accumulate into
    a DENSE [lr, lcB] buffer, and extract ONCE at the end with the
    windowed output-driven extraction (``ops.spgemm.sparsify_windowed``
    — ~2 contiguous-window ops per output slot; the round-2 searchsorted
    extraction cost 26+ s at scale 14 and is gone).  This is the
    "dense-block strategy for heavy columns" SURVEY §7 hard-part (b),
    taken to whole tiles.

    Returns (C, overflow) like ``summa_spgemm_scan`` (overflow = max tile
    nonzero count minus out_capacity; exact counts even when truncating).
    SUMMA3D layers compose the same way (per-layer tiles are smaller).
    """
    from ..ops.pallas_kernels import semiring_matmul
    from ..ops.spgemm import densify, sparsify_windowed

    _check_compat(A, B)
    if obs.ENABLED:
        obs.count("trace.summa_spgemm_mxu", mode=mode)
    kind = _PALLAS_KINDS.get(sr.name)
    assert kind is not None, (
        f"summa_spgemm_mxu supports semirings {sorted(_PALLAS_KINDS)}; "
        f"got {sr.name} (use summa_spgemm/summa_spgemm_scan)"
    )
    grid = A.grid
    p = grid.pr
    lrA, lcA = A.local_rows, A.local_cols
    lrB, lcB = B.local_rows, B.local_cols
    pm, pk, pn = _pad128(lrA), _pad128(lcA), _pad128(lcB)
    zero = float(np.asarray(sr.zero_fn(A.vals.dtype)))  # static python scalar

    def body(ar, ac, av, an, br, bc, bv, bn):
        a_mine = A.local_tile(ar, ac, av, an)
        b_mine = B.local_tile(br, bc, bv, bn)
        a_stages = _gather_stage_tiles(a_mine, COL_AXIS, p)
        b_stages = _gather_stage_tiles(b_mine, ROW_AXIS, p)
        acc = jnp.full((pm, pn), zero, A.vals.dtype)
        for s in range(p):
            with jax.named_scope("sq.densify"):
                da = densify(a_stages[s], pm, pk, zero)
                db = densify(b_stages[s], pk, pn, zero)
            with jax.named_scope("sq.dot"):
                if kind == "plus_times":
                    prod = _mxu_dot(da, db, mode, acc.dtype)
                else:
                    # XLA has no MXU/VPU lowering for tropical rings —
                    # this is where the Pallas dense kernel earns its
                    # keep
                    prod = semiring_matmul(
                        kind, da, db, bm=256, bk=512, bn=256,
                        interpret=interpret,
                    )
                acc = sr.add(acc, prod)
        with jax.named_scope("sq.extract"):
            out, total = sparsify_windowed(
                acc, zero, lrA, lcB, out_capacity)
        worst = jnp.maximum(total - out_capacity, 0)
        worst = lax.pmax(lax.pmax(worst, ROW_AXIS), COL_AXIS)
        return SpParMat._pack_tile(out) + (worst[None, None],)

    r, c, v, n, overflow = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 8,
        out_specs=(TILE_SPEC,) * 5,
        check_vma=False,
    )(A.rows, A.cols, A.vals, A.nnz, B.rows, B.cols, B.vals, B.nnz)
    mat = SpParMat(
        rows=r, cols=c, vals=v, nnz=n,
        nrows=A.nrows, ncols=B.ncols, grid=grid,
    )
    return mat, overflow[0, 0]


#: Above this local tile dimension the router leaves the whole-tile
#: dense path.  The threshold was set on a machine that is gone, where
#: the sparse-output EXTRACTION was said to cost "~3 s+ per 20M
#: entries"; on the v5e the matmul runs at 157 TFLOP/s (``_mxu_dot``:
#: a scale-14 tile squares in 56 ms) and the extraction, one sort of a
#: window's cells since PR 40, at 116 ms a [4096, 8192] window
#: (``ops/spgemm.py:sparsify_windowed``; my chip runs, PR 40).  Not
#: re-derived here: moving it is a ``perf_opt`` issue's, with the cell
#: to show it.
MXU_MAX_TILE_DIM = 8192


#: Windowed-tier envelope. A dense product costs by the CELL whatever
#: its count (the matrix unit's passes, and the extraction's one scan of
#: every launched window) and the ESC/scan sort by the MULTIPLY, so the
#: dense tier loses once the output is sparse enough: the gate requires
#: at most this many cells per symbolic flop.  Where the two cross on
#: one TPU v5 lite at n = 2^14, 2^28 cells (my chip runs, PR 46,
#: 2026-10-01, host clock, warm, every reading three times within
#: 0.3%).  A clustering iteration (``mcl_job``, ``scripts/mcl_loops.py
#: --cells-per-flop 16 64 256 512``): dense 171 ms whatever its count
#: (three bf16 passes, no extraction); ``scan`` 47 ms at 1.9e5
#: multiplies (1,377 cells each), 160 at 9.1e5 (293), 3,689 at 1.03e7
#: (26): they cross near 270 cells a multiply, and a job is 5.64 s with
#: the line at 16, 2.415 at 64 and at 256, 2.420 at 512.  A whole
#: product (``spgemm_job`` under ``bf16``, ``scripts/tier_line.py``):
#: ``windowed`` 547 / 444 / 462 ms against ``scan`` 1,455 / 692 / 433
#: at 9.7e6 / 2.2e6 / 1.0e6 multiplies (28 / 120 / 258 cells each):
#: they cross near 240.  One power of two serves both callers; the
#: ``scatter`` backend shares the line and has no chip reading of its
#: own.
WINDOWED_MAX_CELLS_PER_FLOP = 256.0
#: Per-device dense-tile ceiling for the windowed tier (cells, not
#: bytes): one row-block accumulator plus the extraction pass must stay
#: cheap; 2^33 cells ≈ scale-17 square tiles on one device.
WINDOWED_MAX_TILE_CELLS = 1 << 33
#: Target cells per row-block accumulator (~256 MB f32) and an upper
#: bound on the unrolled block count (program size).
WINDOWED_BLOCK_CELLS = 1 << 26
WINDOWED_MAX_BLOCKS = 32
#: Expansion chunk width for the scatter backend: the scatter pays per
#: SLOT, so the narrow window keeps slot padding ~1.1x on R-MAT degree
#: tails (vs ~2x at the gather-bound ESC default of 32).
WINDOWED_CHUNK_W = 8
#: 2D ``dot`` backend envelope: one stage's dense B COLUMN PANEL
#: (padded k × padded col window) may hold at most this many cells
#: (2^27 ≈ 512 MB f32 / 256 MB bf16).  This is the cap that replaces
#: "B's whole dense tile must fit" — the reason the router can now
#: auto-route ``windowed`` on TPU above the mxu envelope.
WINDOWED_MAX_PANEL_CELLS = 1 << 27
#: Upper bound on the unrolled col-window count (program size, like
#: ``WINDOWED_MAX_BLOCKS`` for row blocks).
WINDOWED_MAX_COL_WINDOWS = 32


def default_block_cols(local_rows_b: int, local_cols_b: int) -> int:
    """Col-window width for the 2D ``dot`` backend: the widest
    512-multiple whose dense B panel (padded-k × window) stays within
    ``WINDOWED_MAX_PANEL_CELLS``, floored so at most
    ``WINDOWED_MAX_COL_WINDOWS`` windows unroll into the program.

    In the extreme region ``pad(k) · lcB > WINDOWED_MAX_COL_WINDOWS ·
    WINDOWED_MAX_PANEL_CELLS`` the two bounds conflict and the window-
    count floor wins (program size is a hard constraint; memory is the
    caller's budget) — the router never auto-routes there
    (``dot_panel_feasible`` gates it to scan), so only forced calls can
    exceed the envelope."""
    pk = _pad128(local_rows_b)
    bc = max((WINDOWED_MAX_PANEL_CELLS // pk) // 512 * 512, 512)
    floor_bc = -(-local_cols_b // WINDOWED_MAX_COL_WINDOWS)
    bc = max(bc, -(-floor_bc // 512) * 512)
    return min(bc, max(local_cols_b, 1))


def dot_panel_feasible(k_dim: int, n_dim: int | None = None) -> bool:
    """True iff a col window exists that fits the stage-operand
    envelope (``WINDOWED_MAX_PANEL_CELLS``) WITHOUT exceeding the
    unrolled-window budget: the narrowest admissible window is 512
    cols, raised to ``ceil(n / WINDOWED_MAX_COL_WINDOWS)`` when B's
    tile width is known (``default_block_cols`` floors there to bound
    program size, so the envelope must hold at that width too)."""
    win = 512
    if n_dim is not None:
        floor_bc = -(-n_dim // WINDOWED_MAX_COL_WINDOWS)
        win = max(win, -(-floor_bc // 512) * 512)
    return _pad128(k_dim) * win <= WINDOWED_MAX_PANEL_CELLS


def default_block_rows(local_rows: int, local_cols_b: int) -> int:
    """Row-block height for the windowed tier: close to
    ``WINDOWED_BLOCK_CELLS`` per dense accumulator, at most
    ``WINDOWED_MAX_BLOCKS`` blocks (the static loop is unrolled into the
    program), multiple-of-8 for the extraction's cell groups."""
    pcols = max(-(-local_cols_b // 128) * 128, 1)
    br = max(1, min(local_rows, WINDOWED_BLOCK_CELLS // pcols))
    br = max(br, -(-local_rows // WINDOWED_MAX_BLOCKS))
    return min(-(-br // 8) * 8, max(local_rows, 1))


@partial(
    jax.jit,
    static_argnames=("sr", "rb", "flop_cap", "out_cap", "chunk_w"),
)
def _windowed_block_local(
    sr: Semiring,
    a: SpTuples,
    b_csr,
    lo,
    *,
    rb: int,
    flop_cap: int,
    out_cap: int,
    chunk_w: int,
):
    """One row block of the LOCAL windowed tier (see
    ``local_spgemm_windowed``).  ``lo`` is a TRACED scalar so blocks with
    the same (rb, caps) signature share one compiled program."""
    from ..ops.spgemm import (
        accumulate_block_scatter,
        mask_rows,
        sparsify_windowed,
    )

    lrA, lcB = a.nrows, b_csr.ncols
    pcols = -(-lcB // 128) * 128
    zero = sr.zero(a.vals.dtype)
    with jax.named_scope("sq.dot"):
        am = mask_rows(a, lo, lo + rb)
        acc = jnp.full((rb, pcols), zero, a.vals.dtype)
        acc = accumulate_block_scatter(
            sr, acc, am, b_csr, row_lo=lo, flop_capacity=flop_cap,
            chunk_w=chunk_w,
        )
    with jax.named_scope("sq.extract"):
        t, total = sparsify_windowed(
            acc, float(np.asarray(sr.zero_fn(a.vals.dtype))), rb, lcB,
            out_cap,
        )
        rows = jnp.where(t.valid_mask(), t.rows + lo, lrA)
    return rows, t.cols, t.vals, t.nnz, total


@jax.jit
def _local_csr(t: SpTuples) -> CSR:
    with jax.named_scope("sq.densify"):
        return CSR.from_tuples(t)


@partial(jax.jit, static_argnames=("block_cols",))
def _colmajor_with_starts(t: SpTuples, block_cols: int):
    """Col-major-sorted tile + per-window CSC slot starts (the panel
    slicing preamble of the 2D dot backend, hoisted out of the per-block
    programs on the local fast path)."""
    with jax.named_scope("sq.densify"):
        ts = t.sort_colmajor()
        ncw = -(-t.ncols // block_cols)
        bounds = jnp.minimum(
            jnp.arange(ncw + 1, dtype=jnp.int32) * block_cols, t.ncols
        )
        starts = jnp.searchsorted(ts.cols, bounds, side="left").astype(
            jnp.int32
        )
    return ts, starts


@partial(
    jax.jit,
    static_argnames=(
        "sr", "rb", "out_caps_row", "skip_row", "block_cols", "pk",
        "pwin", "panel_cap", "mode", "interpret",
    ),
)
def _windowed_block_local_dot(
    sr: Semiring,
    a: SpTuples,
    bs: SpTuples,
    b_starts,
    lo,
    *,
    rb: int,
    out_caps_row: tuple,
    skip_row: tuple,
    block_cols: int,
    pk: int,
    pwin: int,
    panel_cap: int,
    mode: str,
    interpret: bool,
):
    """One ROW BLOCK of the local 2D ``dot`` tier: all of the block's
    non-skipped col windows in one small program (single device → single
    stage, so the accumulator is the stage product itself).  ``lo`` is
    traced so blocks with the same static signature share a compile."""
    from ..ops.spgemm import densify_combine, mask_rows, sparsify_windowed

    lrA, lcB = a.nrows, bs.ncols
    kind = _PALLAS_KINDS[sr.name]
    arows = _pad128(rb)
    zero = float(np.asarray(sr.zero_fn(a.vals.dtype)))
    with jax.named_scope("sq.densify"):
        am = mask_rows(a, lo, lo + rb)
        da = densify_combine(
            sr, _shift_rowblock(am, lo, arows), arows, pk)
    rows_l, cols_l, vals_l = [], [], []
    nnz = jnp.int32(0)
    worst = jnp.int32(0)
    for h in packed_windows(skip_row):  # packed launch list
        with jax.named_scope("sq.densify"):
            panel = _dense_col_panel(
                sr, bs, b_starts, h, block_cols, pk, pwin, panel_cap
            )
        with jax.named_scope("sq.dot"):
            prod = _window_stage_product(
                sr, kind, da, panel, mode, interpret)
        wc = min(block_cols, lcB - h * block_cols)
        with jax.named_scope("sq.extract"):
            t, total = sparsify_windowed(
                prod, zero, rb, wc, out_caps_row[h])
            worst = jnp.maximum(worst, total - out_caps_row[h])
            vm = t.valid_mask()
            rows_l.append(jnp.where(vm, t.rows + lo, lrA))
            cols_l.append(jnp.where(vm, t.cols + h * block_cols, lcB))
            vals_l.append(t.vals)
            nnz = nnz + t.nnz
    return (
        jnp.concatenate(rows_l), jnp.concatenate(cols_l),
        jnp.concatenate(vals_l), nnz, worst,
    )


def local_spgemm_windowed(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    block_rows: int,
    flop_caps: tuple,
    out_caps: tuple,
    skip: tuple,
    chunk_w: int = 8,
    backend: str = "scatter",
    block_cols: int | None = None,
    panel_cap: int | None = None,
    mode: str = "f32",
    interpret: bool = False,
) -> tuple[SpParMat, jax.Array]:
    """Single-device (1x1 grid) fast path of the windowed tier: a HOST
    loop dispatching one small compiled program PER ROW BLOCK instead of
    the one fused shard_map graph.

    Measured on XLA:CPU at scale 16 (round 6): the
    32-block fused program runs 340 s while the same work as separate
    per-block programs runs ~100 s — the giant graph defeats the
    scheduler (and shard_map adds another layer even on one device), so
    on a single device the unfused dispatch is the honest kernel.  The
    shard_map kernel (``summa_spgemm_windowed``) remains the multi-device
    path where the stage collectives must live inside one program.

    Same plan/caps contract and return shape as ``summa_spgemm_windowed``.
    ``backend="dot"`` requires ``block_cols``/``panel_cap`` and 2D caps
    from ``windowed_plan_2d`` — each row block's program covers its
    non-skipped col windows (``_windowed_block_local_dot``).
    """
    assert A.grid.size == 1 and B.grid.size == 1
    _check_compat(A, B)
    lrA, lcB = A.local_rows, B.local_cols
    a = A.local_tile(A.rows, A.cols, A.vals, A.nnz)
    bt = B.local_tile(B.rows, B.cols, B.vals, B.nnz)
    if backend == "dot":
        assert block_cols is not None and panel_cap is not None
        bs, b_starts = _colmajor_with_starts(bt, block_cols)
        _publish_opnames(_colmajor_with_starts, bt, block_cols)
        pk = _pad128(B.local_rows)
        pwin = _pad128(block_cols)
    else:
        assert backend == "scatter", backend
        b_csr = _local_csr(bt)
        _publish_opnames(_local_csr, bt)
    rows_l, cols_l, vals_l = [], [], []
    nnz = None
    worst = jnp.int32(0)
    for g, (fc, oc, sk) in enumerate(zip(flop_caps, out_caps, skip)):
        if (all(sk) if backend == "dot" else sk):
            continue
        lo = g * block_rows
        rb = min(block_rows, lrA - lo)
        if backend == "dot":
            kw = dict(
                rb=rb, out_caps_row=oc, skip_row=sk, block_cols=block_cols,
                pk=pk, pwin=pwin, panel_cap=panel_cap, mode=mode,
                interpret=interpret,
            )
            r, c, v, nz, over = _windowed_block_local_dot(
                sr, a, bs, b_starts, jnp.int32(lo), **kw)
            _publish_opnames(
                _windowed_block_local_dot, sr, a, bs, b_starts,
                jnp.int32(lo), nth=len(rows_l), **kw)
            rows_l.append(r)
            cols_l.append(c)
            vals_l.append(v)
            nnz = nz if nnz is None else nnz + nz
            worst = jnp.maximum(worst, over)
            continue
        kw = dict(rb=rb, flop_cap=max(fc, chunk_w), out_cap=oc,
                  chunk_w=chunk_w)
        r, c, v, nz, total = _windowed_block_local(
            sr, a, b_csr, jnp.int32(lo), **kw)
        _publish_opnames(
            _windowed_block_local, sr, a, b_csr, jnp.int32(lo),
            nth=len(rows_l), **kw)
        rows_l.append(r)
        cols_l.append(c)
        vals_l.append(v)
        nnz = nz if nnz is None else nnz + nz
        worst = jnp.maximum(worst, total - oc)
    if not rows_l:
        t = SpTuples.empty(lrA, lcB, 1, A.vals.dtype)
        rows_l, cols_l, vals_l = [t.rows], [t.cols], [t.vals]
        nnz = t.nnz
    rows = jnp.concatenate(rows_l)
    cols = jnp.concatenate(cols_l)
    vals = jnp.concatenate(vals_l)
    mat = SpParMat(
        rows=rows[None, None], cols=cols[None, None],
        vals=vals[None, None], nnz=nnz[None, None],
        nrows=A.nrows, ncols=B.ncols, grid=A.grid,
    )
    return mat, worst


@partial(
    jax.jit,
    static_argnames=("sr", "rb", "flop_cap", "out_cap", "chunk_w"),
)
def _windowed_block_dist(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    lo,
    *,
    rb: int,
    flop_cap: int,
    out_cap: int,
    chunk_w: int,
):
    """One row block of the BLOCKED-DISPATCH distributed windowed tier
    (scatter backend): a self-contained shard_map program that gathers
    the stage tiles, accumulates ONE dense row block, and extracts it.
    ``lo`` is traced so blocks sharing (rb, caps) share a compile (the
    ``_windowed_block_local`` convention, distributed)."""
    from ..ops.spgemm import accumulate_block_scatter, mask_rows

    grid = A.grid
    p = grid.pr
    lrA, lcB = A.local_rows, B.local_cols
    pcols = -(-lcB // 128) * 128
    zero = float(np.asarray(sr.zero_fn(A.vals.dtype)))

    def body(lo_, ar, ac, av, an, br, bc, bv, bn):
        lo_ = lo_[0, 0]
        a_mine = A.local_tile(ar, ac, av, an)
        b_mine = B.local_tile(br, bc, bv, bn)
        a_stages = _gather_stage_tiles(a_mine, COL_AXIS, p)
        b_stages = _gather_stage_tiles(b_mine, ROW_AXIS, p)
        acc = jnp.full((rb, pcols), zero, A.vals.dtype)
        for s in range(p):
            am = mask_rows(a_stages[s], lo_, lo_ + rb)
            acc = accumulate_block_scatter(
                sr, acc, am, CSR.from_tuples(b_stages[s]), row_lo=lo_,
                flop_capacity=flop_cap, chunk_w=chunk_w,
            )
        chunk, over = _extract_block_1d(
            acc, zero, lo_, rb, lrA, lcB, out_cap
        )
        over = lax.pmax(lax.pmax(over, ROW_AXIS), COL_AXIS)
        return SpParMat._pack_tile(chunk) + (over[None, None],)

    lo_arr = jnp.broadcast_to(
        jnp.int32(lo), (grid.pr, grid.pc)
    )
    lo_arr = jax.device_put(
        lo_arr, jax.sharding.NamedSharding(grid.mesh, TILE_SPEC)
    )
    return jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 9,
        out_specs=(TILE_SPEC,) * 5,
        check_vma=False,
    )(lo_arr, A.rows, A.cols, A.vals, A.nnz,
      B.rows, B.cols, B.vals, B.nnz)


def summa_spgemm_windowed_blocked(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    block_rows: int,
    flop_caps: tuple,
    out_caps: tuple,
    skip: tuple,
    chunk_w: int = 8,
    serialize: bool = True,
) -> tuple[SpParMat, jax.Array]:
    """BLOCKED-DISPATCH distributed windowed tier (scatter backend): a
    host loop launching one small shard_map program per OCCUPIED row
    block instead of the one fused graph.

    The fused ``summa_spgemm_windowed`` unrolls every block into one
    program; XLA:CPU's scheduler then materializes many multi-GB dense
    accumulators concurrently — at scale 18 on the 2×2 virtual mesh the
    fused graph's live set exceeded 125 GB (r9 capture: OOM), the
    distributed incarnation of the r7 single-device lesson that led to
    ``local_spgemm_windowed``.  Per-block dispatch bounds the live set
    to ONE block's accumulator + expansion per device, at the cost of
    re-gathering the stage tiles per block (nblocks × p × tile bytes —
    noise next to the accumulate).  Blocks sharing (rb, caps) share a
    compile (``lo`` is traced); callers wanting maximal sharing pass
    uniform pow2 caps.

    Same plan/caps contract and output-layout contract as the fused
    kernel (valid slots form a compacted prefix per block).

    ``serialize=True`` (default) blocks on each block program before
    dispatching the next: XLA:CPU's multi-thread collective rendezvous
    deadlocks when device threads interleave DIFFERENT in-flight
    programs' gathers (observed at scale 18 — all threads futex-wait),
    so cross-program async pipelining is traded away; per-block
    dispatch overhead is noise next to the accumulate.  On hardware
    pods with ordered per-device streams, pass ``serialize=False`` to
    let dispatch run ahead."""
    assert len(flop_caps) == len(out_caps) == len(skip)
    lrA = A.local_rows
    parts = []
    nnz = None
    worst = jnp.int32(0)
    for g in packed_windows(skip):
        lo = g * block_rows
        rb = min(block_rows, lrA - lo)
        r, c, v, n, over = _windowed_block_dist(
            sr, A, B, lo, rb=rb,
            flop_cap=max(flop_caps[g], chunk_w),
            out_cap=out_caps[g], chunk_w=chunk_w,
        )
        if serialize:
            jax.block_until_ready(n)
        parts.append((r, c, v))
        nnz = n if nnz is None else nnz + n
        worst = jnp.maximum(worst, over[0, 0])
    if not parts:
        empty = SpParMat.from_global_coo(
            A.grid, np.zeros(0, np.int64), np.zeros(0, np.int64),
            np.zeros(0, A.vals.dtype), A.nrows, B.ncols,
        )
        return empty, jnp.int32(0)
    mat = SpParMat(
        rows=jnp.concatenate([p[0] for p in parts], axis=2),
        cols=jnp.concatenate([p[1] for p in parts], axis=2),
        vals=jnp.concatenate([p[2] for p in parts], axis=2),
        nnz=nnz, nrows=A.nrows, ncols=B.ncols, grid=A.grid,
    )
    return mat, worst


def resolve_spgemm_backend(backend: str | None = None) -> str:
    """Accumulate-backend resolution, shared by the router and the sized
    entries: the argument, else the platform default (``dot`` on TPU —
    no scatter unit — ``scatter`` elsewhere)."""
    if backend is None:
        backend = "dot" if jax.default_backend() == "tpu" else "scatter"
    assert backend in ("dot", "scatter"), backend
    return backend


def bucket_plan_caps(flop_caps, out_caps):
    """Pow2-round a windowed plan's capacities (1D int tuples or the 2D
    nested form) so per-block building-block programs share compiles:
    two blocks — or two PRODUCTS inside one shape bucket — whose caps
    round to the same powers of two hit one executable instead of
    compiling per exact count.  Caps are upper bounds, so rounding UP
    is always safe (≤2x extraction slots); callers that know the dense
    block geometry re-impose the cells clamp afterwards (the pow2 round
    can exceed a tail block's dense bound — see ``spgemm_windowed``).
    This is the r7/r9 per-block-program lesson generalized to the
    default path."""
    rnd = lambda x: 1 << (max(int(x), 1) - 1).bit_length()

    def walk(t):
        return tuple(
            walk(x) if isinstance(x, tuple) else rnd(x) for x in t
        )

    return walk(flop_caps), walk(out_caps)


def panel_cap_from_bnnz(bnnz, capacity: int) -> int:
    """Static panel slice capacity from the per-(tile, window) B nnz
    counts: pow2-rounded max (compile reuse across inputs), clamped to
    the tile capacity (a slice can never hold more slots than exist)."""
    m = int(np.asarray(bnnz).max())
    return max(min(1 << max(m - 1, 1).bit_length(), capacity), 1)


def _oracle_out_caps_2d(
    sr, A: SpParMat, B: SpParMat, block_rows: int, block_cols: int,
    out_caps: tuple, skip: tuple,
) -> tuple[tuple, tuple]:
    """Tighten the 2D plan with the bit-packed support oracle
    (``spgemm_support_bits`` → ``support_window_counts``): per-window
    out caps become EXACT output counts instead of clamped-flops bounds
    (smaller extraction capacities / tighter col-window occupancy).
    Single-device only (the oracle computes a whole-matrix mask), and
    only sensible inside its dense envelope — callers gate on size."""
    from ..ops.spgemm import spgemm_support_bits, support_window_counts

    assert A.grid.size == 1 and block_cols % 32 == 0
    a = A.local_tile(A.rows, A.cols, A.vals, A.nnz)
    b = B.local_tile(B.rows, B.cols, B.vals, B.nnz)
    bits, _ = spgemm_support_bits(a, b)
    cnt = np.asarray(
        jax.device_get(
            support_window_counts(
                bits, block_rows, block_cols, A.local_rows, B.local_cols
            )
        )
    )
    new_caps, new_skip = [], []
    for g in range(len(out_caps)):
        row_c, row_s = [], []
        for h in range(len(out_caps[g])):
            exact = int(cnt[g, h])
            row_c.append(max(min(out_caps[g][h], exact), 1))
            row_s.append(bool(skip[g][h] or exact == 0))
        new_caps.append(tuple(row_c))
        new_skip.append(tuple(row_s))
    return tuple(new_caps), tuple(new_skip)


@dataclasses.dataclass(frozen=True)
class WindowedPlan:
    """What one symbolic pass decided for the windowed tier: the window
    geometry, the per-window static capacities and the skip list
    (``windowed_plan`` for ``scatter``: 1D tuples; ``windowed_plan_2d``
    for ``dot``: a tuple of per-block tuples, plus the B panel's slice
    capacity).  ``per_true`` keeps the true symbolic counts the plan
    was made from (the density gauges read them).  The same operands
    give the same plan, so a repeated product compiles nothing."""

    backend: str
    block_rows: int
    block_cols: int | None
    flop_caps: tuple
    out_caps: tuple
    skip: tuple
    panel_cap: int | None
    per_true: np.ndarray

    def chunk_caps(self) -> tuple[int, ...]:
        """The output capacities of the launched windows, in the order
        the kernels lay their chunks in the result: row blocks
        (``scatter``), or (row block, col window) pairs block-major
        (``dot``)."""
        if self.backend == "dot":
            return tuple(
                self.out_caps[g][h] for g, h in packed_windows_2d(self.skip))
        return tuple(self.out_caps[g] for g in packed_windows(self.skip))

    def windows(self) -> tuple[int, int]:
        """``(launched, skipped)`` windows."""
        launched = len(self.chunk_caps())
        total = sum(
            len(row) if self.backend == "dot" else 1 for row in self.skip)
        return launched, total - launched


def plan_windowed(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    backend: str,
    block_rows: int | None = None,
    block_cols: int | None = None,
    slack: float = 1.02,
    oracle: bool = False,
) -> WindowedPlan:
    """The windowed tier's symbolic pass: device counts, read back to
    the host and turned into a ``WindowedPlan`` (every host readback of
    the tier's sizing is in here; ``run_windowed`` then launches only).
    The capacities are rounded up to powers of two
    (``bucket_plan_caps``) and clamped to their window's cells."""
    if block_rows is None:
        block_rows = default_block_rows(A.local_rows, B.local_cols)
    if backend == "dot":
        if block_cols is None:
            block_cols = default_block_cols(B.local_rows, B.local_cols)
        # chunk_w=1 (identity padding): the dot backend never consumes
        # the padded counts, so the symbolic pass runs its inner
        # gather+segment loop once instead of twice
        pair = host_value(
            summa_window_flops_pair(
                A, B, block_rows, block_cols, chunk_w=1
            )
        )
        _publish_opnames(
            summa_window_flops_pair, A, B, block_rows, block_cols,
            chunk_w=1)
        pt = pair[1]
        flop_caps, out_caps, skip = windowed_plan_2d(
            None, pt, block_rows, block_cols,
            A.local_rows, B.local_cols, slack=slack,
        )
        if oracle:
            # the oracle densifies FULL bf16 supports (spgemm_support
            # _bits) — only admissible inside the mxu-tier size
            # envelope, on one device, with word-aligned windows
            if (
                A.grid.size == 1
                and block_cols % 32 == 0
                and max(A.local_rows, B.local_rows, B.local_cols)
                <= MXU_MAX_TILE_DIM
            ):
                out_caps, skip = _oracle_out_caps_2d(
                    sr, A, B, block_rows, block_cols, out_caps, skip
                )
            else:
                # requested but inapplicable: fall back to the
                # clamped-flops caps, observably (never silently)
                if obs.ENABLED:
                    obs.count("spgemm.windowed.oracle_skipped")
        # pow2 caps AFTER oracle tightening: the bucket keeps the
        # compile-sharing property, the oracle keeps the skips; then
        # re-impose the dense-window bound the round may have exceeded
        # on tail blocks/windows (no slot can outnumber the window's
        # cells)
        flop_caps, out_caps = bucket_plan_caps(flop_caps, out_caps)
        out_caps = tuple(
            tuple(
                min(
                    oc,
                    max(min(block_rows,
                            A.local_rows - g * block_rows), 1)
                    * max(min(block_cols,
                              B.local_cols - h * block_cols), 1),
                )
                for h, oc in enumerate(row)
            )
            for g, row in enumerate(out_caps)
        )
        panel_cap = panel_cap_from_bnnz(
            host_value(summa_window_bnnz(B, block_cols)),
            int(B.capacity),
        )
        _publish_opnames(summa_window_bnnz, B, block_cols)
        return WindowedPlan(
            "dot", block_rows, block_cols, flop_caps, out_caps, skip,
            panel_cap, np.asarray(pt),
        )
    assert backend == "scatter", backend
    # one symbolic pass yields both the padded (expansion-capacity) and
    # true (output-bound) counts
    pair = host_value(
        summa_rowblock_flops_pair(
            A, B, block_rows, chunk_w=WINDOWED_CHUNK_W
        )
    )
    _publish_opnames(
        summa_rowblock_flops_pair, A, B, block_rows,
        chunk_w=WINDOWED_CHUNK_W)
    pb, pt = pair[0], pair[1]
    flop_caps, out_caps, skip = windowed_plan(
        pb, pt, block_rows, A.local_rows, B.local_cols, slack=slack
    )
    flop_caps, out_caps = bucket_plan_caps(flop_caps, out_caps)
    # dense-block bound re-imposed after the pow2 round (tail blocks:
    # rb * lcB may not be a power of two)
    out_caps = tuple(
        min(
            oc,
            max(min(block_rows, A.local_rows - g * block_rows), 1)
            * B.local_cols,
        )
        for g, oc in enumerate(out_caps)
    )
    return WindowedPlan(
        "scatter", block_rows, None, flop_caps, out_caps, skip, None,
        np.asarray(pt),
    )


def run_windowed(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    plan: WindowedPlan,
    *,
    mode: str = "f32",
    interpret: bool = False,
    ring: bool = False,
    pipeline: bool = True,
    dispatch: str = "auto",
) -> SpParMat:
    """The windowed tier's numeric phase under a ``WindowedPlan``: the
    kernel the backend, the grid and ``dispatch`` select, closed by the
    host's read of the overflow flag (the plan's caps are symbolic
    UPPER bounds, so an overflow is a bug, never a retry)."""
    backend, block_rows, block_cols = (
        plan.backend, plan.block_rows, plan.block_cols
    )
    flop_caps, out_caps, skip = plan.flop_caps, plan.out_caps, plan.skip
    pt = plan.per_true
    chunk_w = WINDOWED_CHUNK_W
    if backend == "dot":
        if obs.ENABLED:
            obs.count(
                "spgemm.windowed.dispatch",
                mode="local" if A.grid.size == 1 else "fused",
            )
            nsk = sum(sum(row) for row in skip)
            obs.count("spgemm.windowed.col_windows_skipped", nsk)
            npk = len(packed_windows_2d(skip))
            ntot = sum(len(row) for row in skip)
            obs.count("spgemm.windowed.windows_packed", npk)
            obs.gauge(
                "spgemm.windowed.pack_ratio",
                npk / ntot if ntot else 0.0,
            )
            obs.gauge(
                "spgemm.windowed.col_windows",
                len(skip[0]) if skip else 0,
            )
            obs.gauge(
                "spgemm.windowed.panel_cells",
                _pad128(B.local_rows) * _pad128(block_cols),
            )
            obs.gauge("spgemm.windowed.blocks", len(skip))
            # per-window symbolic mask density, averaged over the LIVE
            # windows (the 2D analog of spgemm.auto.mask_density)
            live_cells = live_bound = 0.0
            per_tile = np.asarray(pt).sum(axis=2).max(axis=(-1, -2))
            for g in range(len(skip)):
                rb = min(block_rows, A.local_rows - g * block_rows)
                for h in range(len(skip[g])):
                    if skip[g][h]:
                        continue
                    wc = min(
                        block_cols, B.local_cols - h * block_cols
                    )
                    live_cells += rb * wc
                    live_bound += min(float(per_tile[g, h]), rb * wc)
            obs.gauge(
                "spgemm.windowed.window_density",
                live_bound / live_cells if live_cells else 0.0,
            )
            obs.gauge(
                "spgemm.auto.mask_density",
                live_bound / max(A.local_rows * B.local_cols, 1),
            )
        if A.grid.size == 1:
            C, overflow = local_spgemm_windowed(
                sr, A, B, block_rows=block_rows, flop_caps=flop_caps,
                out_caps=out_caps, skip=skip, backend="dot",
                block_cols=block_cols, panel_cap=plan.panel_cap,
                mode=mode, interpret=interpret,
            )
        else:
            kw = dict(
                block_rows=block_rows, flop_caps=flop_caps,
                out_caps=out_caps, skip=skip, backend="dot", mode=mode,
                chunk_w=chunk_w, interpret=interpret,
                block_cols=block_cols, panel_cap=plan.panel_cap,
                ring=ring, pipeline=pipeline,
            )
            C, overflow = summa_spgemm_windowed(sr, A, B, **kw)
            _publish_opnames(summa_spgemm_windowed, sr, A, B, **kw)
        over = int(overflow)
        assert over <= 0, (
            f"windowed tier overflowed its symbolic bound by {over}"
        )
        _record_realized_nnz(C)
        return C
    # the building-block decomposition rule (round 10): any distributed
    # scatter product with >1 occupied block defaults to per-block
    # programs — the ring carousel is a fused-only schedule, so a ring
    # request keeps the fused graph even against dispatch="blocked"
    # (the more specific schedule ask wins; the conflict is counted)
    if ring and dispatch == "blocked":
        if obs.ENABLED:
            obs.count("spgemm.windowed.dispatch_conflict")
        dispatch = "fused"
    use_blocked = (
        A.grid.size > 1
        and backend == "scatter"
        and (
            dispatch == "blocked"
            or (
                dispatch == "auto"
                and not ring
                and len(packed_windows(skip)) > 1
            )
        )
    )
    if obs.ENABLED:
        obs.count(
            "spgemm.windowed.dispatch",
            mode=(
                "blocked" if use_blocked
                else "local" if A.grid.size == 1
                else "fused"
            ),
        )
    if obs.ENABLED:
        obs.count("spgemm.windowed.windows_skipped", sum(skip))
        npk = len(packed_windows(skip))
        obs.count("spgemm.windowed.windows_packed", npk)
        obs.gauge(
            "spgemm.windowed.pack_ratio",
            npk / len(skip) if skip else 0.0,
        )
        obs.gauge("spgemm.windowed.blocks", len(skip))
        cells = max(A.local_rows * B.local_cols, 1)
        obs.gauge(
            "spgemm.auto.mask_density",
            float(np.asarray(pt).sum(axis=1).max(axis=(-1, -2)).sum())
            / cells,
        )
    if A.grid.size == 1 and backend == "scatter":
        # single-device fast path: per-block programs (the fused
        # shard_map graph measures >2x slower on XLA:CPU — see
        # local_spgemm_windowed)
        C, overflow = local_spgemm_windowed(
            sr, A, B, block_rows=block_rows, flop_caps=flop_caps,
            out_caps=out_caps, skip=skip, chunk_w=chunk_w,
        )
    elif use_blocked:
        # distributed building-block dispatch: one small shard_map
        # program per occupied row block, bucketed caps shared — the
        # default that bounds first-touch compile AND the live set
        C, overflow = summa_spgemm_windowed_blocked(
            sr, A, B, block_rows=block_rows, flop_caps=flop_caps,
            out_caps=out_caps, skip=skip, chunk_w=chunk_w,
        )
    else:
        C, overflow = summa_spgemm_windowed(
            sr, A, B, block_rows=block_rows, flop_caps=flop_caps,
            out_caps=out_caps, skip=skip, backend=backend, mode=mode,
            chunk_w=chunk_w, interpret=interpret, ring=ring,
            pipeline=pipeline,
        )
    over = int(overflow)
    # out_caps are symbolic UPPER bounds — overflow means the symbolic
    # pass disagreed with the kernel (a bug), not an underestimate
    assert over <= 0, f"windowed tier overflowed its symbolic bound by {over}"
    _record_realized_nnz(C)
    return C


def spgemm_windowed(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    block_rows: int | None = None,
    block_cols: int | None = None,
    backend: str | None = None,
    mode: str = "f32",
    slack: float = 1.02,
    interpret: bool = False,
    oracle: bool = False,
    ring: bool = False,
    pipeline: bool = True,
    dispatch: str = "auto",
) -> SpParMat:
    """Sized entry for the windowed tier: device symbolic pass →
    ``windowed_plan`` (scatter, 1D) or ``windowed_plan_2d`` (dot, 2D) →
    the matching kernel (one host readback for sizing).  Reads no
    environment variable.

    ``dispatch`` picks the multi-device program decomposition for the
    scatter backend: ``"auto"`` (default) routes any product with more
    than one occupied row block through the BLOCKED building-block
    dispatch (``summa_spgemm_windowed_blocked`` — one small fixed-shape
    program per occupied block, caps pow2-bucketed so blocks share
    compiles), which bounds both first-touch compile time and the live
    set: no single XLA compile scales with the whole product (the
    scale-17 54-minute fused-compile wall cannot recur).  ``"fused"``
    forces the one-graph kernel (required by — and implied for — the
    ``ring`` carousel schedules); ``"blocked"`` forces per-block
    programs.  Single-device products already run per-block programs
    (``local_spgemm_windowed``); the dot backend's multi-device path
    is the fused kernel whatever ``dispatch`` says: at the mesh cell's
    size (a ``[16384, 16384]`` tile, four row blocks by two windows by
    two stages) the v5e compiler takes it in a minute and a job peaks
    at 4.4 GB a chip (PR 48), so it has needed no blocked form.

    ``oracle=True`` (dot, single device, inside the support-oracle
    envelope) replaces the clamped-flops out caps with the EXACT
    per-window output counts from the bit-packed support oracle — which
    also SHRINKS the packed launch list: flops-positive but
    output-empty windows become skips, so the kernel pays one MXU
    launch per genuinely occupied window
    (``spgemm.windowed.windows_packed`` / ``.pack_ratio``).

    ``ring=True`` (multi-device only) runs the stage-pipelined carousel
    schedule instead of the gathered one; ``pipeline=False`` pins the
    serial-chain control (see ``summa_spgemm_windowed``).
    """
    assert dispatch in ("auto", "fused", "blocked"), dispatch
    plan = plan_windowed(
        sr, A, B, backend=resolve_spgemm_backend(backend),
        block_rows=block_rows, block_cols=block_cols, slack=slack,
        oracle=oracle,
    )
    return run_windowed(
        sr, A, B, plan, mode=mode, interpret=interpret, ring=ring,
        pipeline=pipeline, dispatch=dispatch,
    )


def coo_has_duplicates(M: SpParMat) -> bool:
    """True iff any tile holds a repeated (row, col) entry — the cheap
    nnz-vs-dedup check guarding the mxu tier's unique-entries
    precondition (``densify``'s unique_indices scatter).  One two-key
    sort per tile + one host readback; only spent where a densifying
    unique-indices tier is about to be chosen, and memoized on the
    matrix object so iterative callers (warm-plan serving, algorithm
    loops re-routing the same operand) pay the sort + D2H sync once
    — the readback is a device sync, the expensive part."""
    from ..ops.spgemm import coo_sort_dedup

    cached = getattr(M, "_coo_has_duplicates", None)
    if cached is not None:
        return cached
    lr = M.local_rows

    def body(r, c):
        rows, cols = r[0, 0], c[0, 0]
        rs, _, dup = coo_sort_dedup(rows, cols)
        # padding slots (row == lr) are mutually equal — exclude them
        mine = jnp.sum((dup & (rs < lr)).astype(jnp.int32))
        return lax.psum(lax.psum(mine, ROW_AXIS), COL_AXIS)

    total = jax.shard_map(
        body,
        mesh=M.grid.mesh,
        in_specs=(TILE_SPEC,) * 2,
        out_specs=P(),
        check_vma=False,
    )(M.rows, M.cols)
    result = int(np.asarray(host_value(total))) > 0
    # frozen dataclass: bypass via object.__setattr__ (the attr is not
    # a pytree field, so transforms/copies simply drop it)
    object.__setattr__(M, "_coo_has_duplicates", result)
    return result


def choose_tier_from_counts(
    sr: Semiring,
    max_tile_dim: int,
    tile_cells: int,
    pr: int,
    flops_total: float,
    backend: str | None = None,
    k_dim: int | None = None,
    allow_mxu: bool = True,
    n_dim: int | None = None,
) -> str:
    """Pure tier gate over pre-computed counts — shared by the device
    router (``choose_spgemm_tier``) and ``spgemm_job``.  See
    ``choose_spgemm_tier`` for the rule.  ``k_dim`` is B's local row
    count and ``n_dim`` B's local col count (the dot backend's
    panel-feasibility check — ``dot_panel_feasible``; ``k_dim``
    defaults to ``max_tile_dim``); ``allow_mxu=False`` re-evaluates the
    ladder with the mxu rung removed (the duplicate-entry fallback)."""
    from ..ops.spgemm import scatter_combine_for

    backend = resolve_spgemm_backend(backend)
    if (
        allow_mxu
        and max_tile_dim <= MXU_MAX_TILE_DIM
        and sr.name in _PALLAS_KINDS
    ):
        return "mxu"
    dense_ok = (
        scatter_combine_for(sr) is not None
        and tile_cells <= WINDOWED_MAX_TILE_CELLS
        and tile_cells * pr * pr
        <= WINDOWED_MAX_CELLS_PER_FLOP * max(flops_total, 1.0)
    )
    if backend == "scatter" and dense_ok:
        return "windowed"
    if (
        backend == "dot"
        and dense_ok
        and sr.name in _PALLAS_KINDS
        and dot_panel_feasible(k_dim or max_tile_dim, n_dim)
    ):
        return "windowed"
    return "scan"


def choose_spgemm_tier(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    backend: str | None = None,
    assume_unique: bool = False,
    grid3=None,
) -> str:
    """The routing rule of ``spgemm_auto`` (host-side, observable):

      "mxu"       tiles fit the full-dense MXU envelope, the semiring
                  has a dense kernel, and the tiles hold UNIQUE entries
                  (checked via ``coo_has_duplicates`` unless
                  ``assume_unique`` — duplicate tiles would corrupt the
                  unique-indices densify, so they fall back to the
                  duplicate-absorbing windowed/scan rungs);
      "windowed"  the add monoid has a native scatter combiner, the
                  per-tile dense cell count is bounded, the output is
                  dense enough that one cell scan beats the ESC sort
                  (``WINDOWED_MAX_CELLS_PER_FLOP``), and the backend
                  can accumulate densely: ``scatter`` directly, or
                  ``dot`` (TPU) whenever a 512-wide B column panel fits
                  ``WINDOWED_MAX_PANEL_CELLS`` — the 2D windows bound
                  the stage operand, so TPU mid-scale products now
                  route here instead of falling through to scan;
      "scan"      everything else — output-bounded ESC (the general
                  fallback; exact for every semiring).

    With a LAYERED mesh available (``grid3`` with ``layers > 1`` whose
    layout fits the product — ``mesh3d.summa3d_compatible``), a product
    the 2D rule routes to ``windowed`` upgrades to ``"windowed3d"``:
    the same windowed kernel run per layer on the 3D mesh
    (``spgemm3d_windowed``), where layer replication cuts per-stage
    gather volume L-fold.  Products the 2D rule sends to mxu or scan
    keep their 2D tier (small tiles don't pay conversion; scan-sparse
    outputs would multiply the extraction scans by L).

    Forced override: ``spgemm_auto(tier=...)``; backend by argument,
    else the platform default.  Nothing else decides: no environment
    variable, no file, no timed guess.
    """
    tier = _choose_spgemm_tier_2d(
        sr, A, B, backend=backend, assume_unique=assume_unique
    )
    if grid3 is not None and tier == "windowed":
        from .mesh3d import summa3d_compatible

        if grid3.layers > 1 and summa3d_compatible(
            grid3, A.nrows, A.ncols, B.ncols
        ):
            return "windowed3d"
    return tier


def _choose_spgemm_tier_2d(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    backend: str | None = None,
    assume_unique: bool = False,
) -> str:
    """The 2D rungs of ``choose_spgemm_tier`` (see its docstring)."""
    from ..ops.spgemm import scatter_combine_for

    backend = resolve_spgemm_backend(backend)
    max_dim = max(A.local_rows, A.local_cols, B.local_cols)
    cells = A.local_rows * B.local_cols
    if max_dim <= MXU_MAX_TILE_DIM and sr.name in _PALLAS_KINDS:
        # no symbolic pass needed for this gate — but the unique-entry
        # precondition of the densifying mxu tier must hold, else fall
        # back to a duplicate-absorbing rung (ISSUE 5 guard)
        if assume_unique or not (
            coo_has_duplicates(A)
            or (B is not A and coo_has_duplicates(B))
        ):
            return "mxu"
        if obs.ENABLED:
            obs.count("spgemm.auto.dedup_fallback", sr=sr.name)
        flops_total = float(
            np.asarray(host_value(summa_stage_flops(A, B, padded=False)))
            .astype(np.float64).sum()
        )
        return choose_tier_from_counts(
            sr, max_dim, cells, A.grid.pr, flops_total, backend,
            k_dim=B.local_rows, allow_mxu=False, n_dim=B.local_cols,
        )
    # evaluate every STATIC windowed precondition before paying the
    # symbolic pass: the device pass ends in a host readback (a sync
    # on the caller's path) — never spend it when windowed is structurally
    # ineligible (generic monoids, oversized tiles, infeasible panels)
    if (
        scatter_combine_for(sr) is None
        or cells > WINDOWED_MAX_TILE_CELLS
        or (
            backend == "dot"
            and (
                sr.name not in _PALLAS_KINDS
                or not dot_panel_feasible(B.local_rows, B.local_cols)
            )
        )
    ):
        return "scan"
    flops_total = float(
        np.asarray(host_value(summa_stage_flops(A, B, padded=False)))
        .astype(np.float64).sum()
    )
    return choose_tier_from_counts(
        sr,
        max_dim,
        cells,
        A.grid.pr,
        flops_total,
        backend,
        k_dim=B.local_rows,
        n_dim=B.local_cols,
    )


def spgemm_auto(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    out_capacity: int | None = None,
    slack: float = 1.1,
    max_retries: int = 3,
    mode: str = "f32",
    interpret: bool = False,
    tier: str | None = None,
    block_rows: int | None = None,
    block_cols: int | None = None,
    backend: str | None = None,
    oracle: bool = False,
    assume_unique: bool = False,
    grid3=None,
    ring: bool = False,
    pipeline: bool = True,
    dispatch: str = "auto",
    merge: str | None = None,
) -> SpParMat:
    """Auto-tiered sparse-output SpGEMM: route (shape, density, semiring)
    through the fastest applicable kernel instead of defaulting to ESC.

    Routing: the ``tier`` argument, else ``choose_spgemm_tier`` (which
    ends in ``choose_tier_from_counts``, the rule ``spgemm_job`` is
    measured under).  Nothing else decides: no environment variable, no
    file, no timed guess.  The ladder (see docs/spgemm.md and
    ``choose_spgemm_tier``):

      "mxu"      full-dense MXU stage products + one windowed extraction
                 (small tiles, dense-kernel semirings);
      "windowed" dense WINDOW accumulators (scatter row blocks, or MXU
                 row-block × col-window 2D stage products) +
                 symbolically-sized windowed extraction with empty
                 windows skipped — the general mid-scale tier that
                 removes the ESC sort, on every backend;
      "scan"/"esc"  output-bounded / classic ESC (general fallback).

    ``backend`` forces the windowed accumulate backend (else the
    platform default, ``resolve_spgemm_backend``); ``block_rows`` /
    ``block_cols`` override the window geometry (else
    ``default_block_rows`` / ``default_block_cols``); ``ring`` /
    ``pipeline`` / ``dispatch`` thread through to the windowed tier's
    schedule and program decomposition (see ``spgemm_windowed``);
    ``merge`` (sort | runs | hash) is the combine-merge tier of the
    merge-consuming tiers (the esc stage-chunk combine, the windowed3d
    fiber reduce), resolved where it is used.  The chosen tier is
    recorded as the labeled ``spgemm.auto.tier`` counter, with
    ``spgemm.windowed.windows_skipped`` /
    ``spgemm.windowed.col_windows_skipped`` /
    ``spgemm.windowed.window_density`` / ``spgemm.auto.mask_density``
    exposing the skip lists and symbolic output density.

    ``mode`` sets the dense plus_times precision (see ``_mxu_dot``):
    "f32" (exact, slow MXU path), "bf16" (13.3 TFLOP/s — exact for
    bf16-representable values like 0/1 adjacency with counts < 2^24),
    "bf16x3" (split-float, f32-grade error, ~4x faster than f32).
    ``oracle=True`` lets the dot-backend windowed tier tighten its
    per-window extraction caps with the bit-packed support oracle.

    PRECONDITION (mxu tier only): input tiles must hold UNIQUE
    (row, col) entries — ``densify``'s scatter declares
    ``unique_indices`` and duplicate slots would combine
    unpredictably.  The router guards this (``coo_has_duplicates``
    check + fallback; skip it with ``assume_unique=True`` on compacted
    inputs).  Every other rung — INCLUDING the windowed tier's ``dot``
    backend, which densifies with the combining scatter
    (``densify_combine``) — absorbs duplicate COO entries exactly.
    """
    if tier is None:
        tier = choose_spgemm_tier(
            sr, A, B, backend=backend, assume_unique=assume_unique,
            grid3=grid3,
        )
    assert tier in ("mxu", "windowed", "scan", "esc", "windowed3d"), tier
    if obs.ENABLED:
        obs.count("spgemm.auto.tier", tier=tier, sr=sr.name)
    with obs.span("spgemm.auto", sr=sr.name, tier=tier):
        if tier == "esc":
            return spgemm(sr, A, B, slack, merge=merge)
        if tier == "scan":
            return spgemm_scan(
                sr, A, B, out_capacity=out_capacity, slack=slack,
                max_retries=max_retries,
            )
        if tier == "windowed":
            return spgemm_windowed(
                sr, A, B, block_rows=block_rows, block_cols=block_cols,
                backend=backend, mode=mode, slack=slack,
                interpret=interpret, oracle=oracle, ring=ring,
                pipeline=pipeline, dispatch=dispatch,
            )
        if tier == "windowed3d":
            # the layered route: 2D operands → 3D splits (on-device
            # redistribution), per-layer windowed SUMMA, fiber reduce,
            # back to the caller's 2D grid — one call, same contract
            assert grid3 is not None, (
                "tier='windowed3d' needs a grid3 (the layered mesh)"
            )
            from .mesh3d import SpParMat3D, spgemm3d_windowed

            A3 = SpParMat3D.from_spmat(A, grid3, split="col")
            B3 = SpParMat3D.from_spmat(B, grid3, split="row")
            # ring/pipeline now reach the per-layer 3D SUMMA too (the
            # round-13 carousel); oracle seeding stays 2D-plan-only
            C3 = spgemm3d_windowed(
                sr, A3, B3, block_rows=block_rows,
                block_cols=block_cols, backend=backend, mode=mode,
                slack=slack, interpret=interpret, merge=merge,
                ring=ring, pipeline=pipeline,
            )
            return C3.to_spmat(A.grid)
        # tier == "mxu": the round-4 whole-tile dense path
        if out_capacity is None:
            out_capacity = max(A.capacity, B.capacity, 64)
        out_capacity = 1 << (int(out_capacity) - 1).bit_length()
        over = 0
        for attempt in range(max_retries + 1):
            C, overflow = summa_spgemm_mxu(
                sr, A, B, out_capacity=out_capacity, mode=mode,
                interpret=interpret,
            )
            over = int(overflow)
            if over <= 0:
                if obs.ENABLED:
                    obs.count("spgemm.mxu.overflow_retries", attempt)
                    _record_realized_nnz(C)
                return C
            out_capacity = 1 << (out_capacity + over - 1).bit_length()
        raise ValueError(
            f"spgemm_auto still overflowing by {over} after {max_retries} "
            "retries; pass an explicit out_capacity"
        )


# ---------------------------------------------------------------------------
# One product as one job
# ---------------------------------------------------------------------------

#: The ``jax.named_scope`` names of a product job's programs, in the
#: order a job meets them.  ``sq.symbolic`` is the counting pass whose
#: result the host reads to size the numeric phase; ``sq.densify`` the
#: scatter of a row block of A and of a column panel of B into dense
#: operands; ``sq.dot`` the stage product on the matrix unit;
#: ``sq.extract`` the walk from a dense product back to tuples
#: (``ops/spgemm.py:sparsify_windowed``) and the sort-and-fold of the
#: tiers that never densify; ``sq.digest`` the job's last program.
#: Trace-time metadata only: the device trace's per-scope times are read
#: by these names (docs/observability.md "Named scopes"), so a rename is
#: a change of yardstick.
SQ_SCOPES = (
    "sq.symbolic",
    "sq.densify",
    "sq.dot",
    "sq.extract",
    "sq.digest",
)

#: What the job's mesh path adds to ``SQ_SCOPES``: ``sq.exchange`` the
#: stage exchange of operand tiles (``_gather_stage_tiles``'s
#: ``all_gather``, the carousel's ``ppermute``) and ``sq.pack`` the cut
#: of every tile to what it stores (``_tile_chunk_counts``,
#: ``_pack_tiles``).  A one-tile job carries neither.
SQ_MESH_SCOPES = ("sq.exchange", "sq.pack")

#: The tiers a job can name, and the accumulate backend a job runs under
#: when none is given: the chip's (``resolve_spgemm_backend``'s platform
#: default on a TPU), on every platform, so a CPU rehearsal runs the
#: chip's program.
JOB_TIERS = ("mxu", "windowed", "scan", "esc")
JOB_BACKEND = "dot"

#: ``h(j) = (j + 1) * DIGEST_MULTIPLIER mod 2^32``: the odd multiplier
#: of the digest's column hash (the golden-ratio constant of Knuth's
#: multiplicative hashing).
DIGEST_MULTIPLIER = 0x9E3779B1


@jax.jit
def spgemm_digest(C: SpParMat):
    """What closes a product job, from the stored tuples of ``C`` and on
    the device: ``(nnz, hilo, counts, sums, prints)``.

    ``counts[i]`` is the number of stored entries of row ``i``,
    ``sums[i]`` their sum and ``prints[i]`` the fingerprint
    ``sum_j C[i, j] * h(j)`` in wrapping uint32 arithmetic (carried as
    int32 bits), ``h`` the fixed odd-multiplier hash of the GLOBAL
    column (``DIGEST_MULTIPLIER``); all three are ``int32[nrows]``,
    replicated.  Values enter as ``int32(C[i, j])``: exact for the
    integer-valued products a digest is for.  ``nnz`` is the stored
    entries in all and ``hilo`` the ``int32[2]`` 15-bit (hi, lo) split
    of the sum of C (``ops/spgemm.py:combine_hilo``), exact while every
    row's sum is below 2^31 and the whole below 2^46.  An entry
    changed moves a sum and a fingerprint; one dropped a count; one
    moved along its row the fingerprint alone, since ``h`` is
    injective on columns."""
    grid = C.grid
    lr, lc = C.local_rows, C.local_cols

    def body(r, c, v):
        rows, cols, vals = r[0, 0], c[0, 0], v[0, 0]
        with jax.named_scope("sq.digest"):
            valid = rows < lr
            seg = jnp.where(valid, rows, lr)
            vi = jnp.where(valid, vals.astype(jnp.int32), 0)
            gcol = cols + lax.axis_index(COL_AXIS) * lc
            h = (gcol.astype(jnp.uint32) + jnp.uint32(1)) * jnp.uint32(
                DIGEST_MULTIPLIER)
            fp = lax.bitcast_convert_type(
                vi.astype(jnp.uint32) * h, jnp.int32)
            # by rows with one sort, two running sums and a search of
            # the sorted rows for every row's end: on the v5e a sort of
            # 33.5 M keys is 81 ms and a running sum 8 ms, where one
            # scatter-add of 40.7 M entries into their rows is 273 ms
            # (the digest as three of those was 1.09 s of a 2.29 s job;
            # my chip runs, PR 40)
            seg, vi, fp = lax.sort(  # sums: any order inside a row
                (seg, vi, fp), num_keys=1, is_stable=False)
            ends = jnp.searchsorted(
                seg, jnp.arange(lr, dtype=jnp.int32), side="right"
            ).astype(jnp.int32)
            starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])

            def by_row(w):  # int32 adds wrap, in the running sum too
                run = jnp.concatenate(
                    [jnp.zeros((1,), jnp.int32), jnp.cumsum(w)])
                return run[ends] - run[starts]

            counts, sums, prints = (
                lax.all_gather(
                    lax.psum(w, COL_AXIS), ROW_AXIS
                ).reshape(-1)[:C.nrows]
                for w in (ends - starts, by_row(vi), by_row(fp))
            )
            # the sum of C from the row sums, in 15-bit halves folded
            # 2^15 rows at a time so no partial sum passes 2^31
            pad = -C.nrows % (1 << 15)
            s2 = jnp.pad(sums, (0, pad)).reshape(-1, 1 << 15)
            lo = jnp.sum(s2 & 0x7FFF, axis=1)
            hi = jnp.sum(s2 >> 15) + jnp.sum(lo >> 15)
            hilo = jnp.stack([hi, jnp.sum(lo & 0x7FFF)])
            return jnp.sum(counts), hilo, counts, sums, prints

    return jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 3,
        out_specs=P(),
        check_vma=False,
    )(C.rows, C.cols, C.vals)


def _prefix_counts(rows, lr: int, caps: tuple):
    """Stored entries (``rows < lr``) of each chunk of one tile's slots,
    and whether every chunk holds them as a prefix."""
    counts, ok, off = [], jnp.bool_(True), 0
    for cap in caps:
        valid = rows[off:off + cap] < lr
        n = jnp.sum(valid, dtype=jnp.int32)
        counts.append(n)
        ok &= ~jnp.any(valid & (jnp.arange(cap, dtype=jnp.int32) >= n))
        off += cap
    return jnp.stack(counts), ok


@partial(jax.jit, static_argnames=("caps",))
def _chunk_counts(C: SpParMat, caps: tuple):
    """Stored entries of each chunk of a one-tile result, and whether
    every chunk holds them as a prefix (``caps``: the chunks' static
    capacities, in the order the tier laid them)."""
    with jax.named_scope("sq.extract"):
        return _prefix_counts(C.rows[0, 0], C.local_rows, caps)


@partial(jax.jit, static_argnames=("caps", "counts"))
def _pack_chunks(C: SpParMat, caps: tuple, counts: tuple) -> SpParMat:
    """A one-tile result whose chunks hold their stored entries as
    prefixes, with the padding between them taken out: static slices
    and one concatenation, capacity = the stored entries."""
    offs = np.concatenate([[0], np.cumsum(caps)[:-1]])
    with jax.named_scope("sq.extract"):
        rows, cols, vals = (
            jnp.concatenate([
                x[0, 0, o:o + n] for o, n in zip(offs, counts) if n
            ])[None, None]
            for x in (C.rows, C.cols, C.vals)
        )
    return dataclasses.replace(C, rows=rows, cols=cols, vals=vals)


@partial(jax.jit, static_argnames=("caps",))
def _tile_chunk_counts(C: SpParMat, caps: tuple):
    """``_chunk_counts`` of every tile of a result on a mesh:
    ``int32[pr, pc, len(caps)]`` (each tile's own, left where the tile
    is), the tiles' totals ``int32[pr, pc]`` (replicated: the host reads
    them) and whether every chunk of every tile holds its entries as a
    prefix."""
    lr = C.local_rows

    def body(r):
        with jax.named_scope("sq.pack"):
            counts, ok = _prefix_counts(r[0, 0], lr, caps)
            ok = lax.pmin(ok.astype(jnp.int32), (ROW_AXIS, COL_AXIS))
            totals = lax.all_gather(
                lax.all_gather(jnp.sum(counts), COL_AXIS), ROW_AXIS)
        return counts[None, None], totals, ok

    return jax.shard_map(
        body, mesh=C.grid.mesh, in_specs=(TILE_SPEC,),
        out_specs=(TILE_SPEC, P(), P()), check_vma=False,
    )(C.rows)


@partial(jax.jit, static_argnames=("caps", "capacity"))
def _pack_tiles(C: SpParMat, counts, caps: tuple, capacity: int) -> SpParMat:
    """Every tile of a result on a mesh with the padding between its
    chunks taken out, all under ONE static ``capacity`` (a program is
    one shape on every device): the chunks, whole, laid one after the
    other at the running sum of the tile's own ``counts``, so each
    covers the padding of the one before; a ``capacity`` below a tile's
    entries would drop some (``_packed`` passes the fullest tile's)."""
    lr, lc = C.local_rows, C.local_cols
    offs = np.concatenate([[0], np.cumsum(caps)[:-1]])
    room = capacity + max(caps)

    def body(r, c, v, cnt):
        cnt = cnt[0, 0]
        starts = jnp.cumsum(cnt) - cnt
        with jax.named_scope("sq.pack"):
            out = []
            for x, fill in ((r[0, 0], lr), (c[0, 0], lc), (v[0, 0], 0)):
                buf = jnp.full((room,), fill, x.dtype)
                for k, (o, cap) in enumerate(zip(offs, caps)):
                    buf = lax.dynamic_update_slice(
                        buf, x[o:o + cap], (starts[k],))
                out.append(buf[:capacity][None, None])
        return (*out, jnp.sum(cnt)[None, None])

    rows, cols, vals, nnz = jax.shard_map(
        body, mesh=C.grid.mesh, in_specs=(TILE_SPEC,) * 4,
        out_specs=(TILE_SPEC,) * 4, check_vma=False,
    )(C.rows, C.cols, C.vals, counts)
    return dataclasses.replace(C, rows=rows, cols=cols, vals=vals, nnz=nnz)


def _packed(C: SpParMat, caps: tuple) -> SpParMat:
    """A tier's capacity-padded result cut to what it stores.
    Every tier sizes its output by a symbolic UPPER bound (the windowed
    tier a window at a time: on a squared R-MAT the bounds add up to the
    dense matrix), and whatever reads the result next pays for its
    capacity, not for its entries.  One small readback (the chunks'
    counts), then on one tile slices at what the host now knows; on a
    mesh every tile is cut under the FULLEST tile's count
    (``_pack_tiles``), which the same operands give again."""
    if C.grid.size > 1:
        counts, totals, ok = _tile_chunk_counts(C, caps)
        totals, ok = host_value(totals), host_value(ok)
        _publish_opnames(_tile_chunk_counts, C, caps)
        assert ok, "a tier's chunk does not hold its entries as a prefix"
        capacity = max(int(totals.max()), 1)
        out = _pack_tiles(C, counts, caps, capacity)
        _publish_opnames(_pack_tiles, C, counts, caps, capacity)
        return out
    counts, ok = jax.device_get(_chunk_counts(C, caps))
    _publish_opnames(_chunk_counts, C, caps)
    assert ok, "a tier's chunk does not hold its entries as a prefix"
    counts = tuple(int(n) for n in counts)
    if not sum(counts):
        return C
    out = _pack_chunks(C, caps, counts)
    _publish_opnames(_pack_chunks, C, caps, counts)
    return out


def spgemm_job(
    sr: Semiring,
    A: SpParMat,
    B: SpParMat,
    *,
    tier: str | None = None,
    backend: str = JOB_BACKEND,
    mode: str = "f32",
    block_rows: int | None = None,
    block_cols: int | None = None,
) -> tuple[SpParMat, dict]:
    """One whole product ``C = A·B`` as an analyst's call times it: from
    the stored operands to C on the device and its digest on the host,
    nothing known beforehand and nothing kept from job to job.  The
    symbolic pass is inside the job; so is the routing where ``tier``
    is None: ``choose_tier_from_counts``'s rule under ``backend``,
    which defaults to the chip's (``JOB_BACKEND``) on every platform.
    Nothing else decides: no environment variable, no file, no timed
    probe; no retry either: every capacity is a symbolic upper bound,
    so a job runs its numeric phase once (an overflow is an
    ``AssertionError``, not a doubling).  The same operands give the
    same static shapes, so a repeated job compiles nothing.

    Returns ``(C, digest)``.  C stays on the device, 2D-distributed as
    the operands are, and is cut to what it stores (``_packed``: the
    tiers return tiles padded to their symbolic bounds, the windowed
    tier's add up to the dense matrix on a squared R-MAT, and the
    digest, like any next step, pays for capacity); on a mesh every tile
    is cut under one capacity, the fullest tile's count, and the
    windowed tier runs ``run_windowed``'s own schedule, the gathered
    one (the chip ran the carousel no faster: ROADMAP D4).  ``digest`` is
    ``spgemm_digest(C)`` read by the host, which closes the job:
    ``nnz`` and ``sum`` (Python ints), ``counts`` / ``sums`` /
    ``prints`` (``int32[nrows]`` numpy), with the ``tier`` and
    ``backend`` the job ran.  It comes back with telemetry off.

    ``mode`` is the dense stage product's input pass (``_mxu_dot``);
    ``block_rows`` / ``block_cols`` override the windowed tier's
    geometry (tests run several windows on a small matrix)."""
    from ..ops.spgemm import combine_hilo, sparsify_groups

    assert backend in ("dot", "scatter"), backend
    with obs.span("spgemm.job", sr=sr.name, backend=backend) as job:
        with obs.span("symbolic"):
            per_stage = host_value(
                summa_stage_flops(A, B, padded=False)
            ).astype(np.float64)
            _publish_opnames(summa_stage_flops, A, B, padded=False)
            products = float(per_stage.sum())
            if tier is None:
                tier = choose_tier_from_counts(
                    sr, max(A.local_rows, A.local_cols, B.local_cols),
                    A.local_rows * B.local_cols, A.grid.pr, products,
                    backend, k_dim=B.local_rows, n_dim=B.local_cols,
                    allow_mxu=not (
                        coo_has_duplicates(A)
                        or (B is not A and coo_has_duplicates(B))
                    ),
                )
            assert tier in JOB_TIERS, tier
            dense_tile = A.local_rows * B.local_cols
            plan, windows, skipped, dense_flops = None, 0, 0, 0
            extract_groups = 0
            if tier == "windowed":
                plan = plan_windowed(
                    sr, A, B, backend=backend, block_rows=block_rows,
                    block_cols=block_cols,
                )
                windows, skipped = plan.windows()

                def rb(g):
                    return min(
                        plan.block_rows, A.local_rows - g * plan.block_rows)

                # the dense [rows, cols] every launched window extracts
                if backend == "dot":
                    shapes = [
                        (_pad128(rb(g)), _pad128(plan.block_cols))
                        for g, _ in packed_windows_2d(plan.skip)]
                    # two flop a cell of every launched window's padded
                    # row block x contraction x col window, a stage (a
                    # tile's own count: what ONE chip issues)
                    dense_flops = 2 * A.grid.pr * _pad128(
                        B.local_rows) * sum(r * c for r, c in shapes)
                else:
                    pcols = _windowed_dims(
                        backend, None, B.local_rows, B.local_cols)[1]
                    shapes = [(rb(g), pcols) for g in packed_windows(plan.skip)]
                extract_groups = sum(
                    sparsify_groups(r, c) for r, c in shapes)
            elif tier in ("scan", "esc"):
                flop_cap, out_cap = summa_capacities(A, B)
                _publish_opnames(summa_stage_flops, A, B)
            else:
                # one output a flop at most, and no more than the tile
                out_cap = _caps_from_stage_flops(
                    per_stage, dense_tile, 1.02)[1]
                dense_flops = 2 * A.grid.pr * _pad128(
                    A.local_rows) * _pad128(A.local_cols) * _pad128(
                    B.local_cols)
            job.annotate(tier=tier)
        with obs.span("numeric"):
            if tier == "windowed":
                C = run_windowed(sr, A, B, plan, mode=mode)
                caps = plan.chunk_caps()
            else:
                if tier == "esc":
                    # pow2 as ``spgemm`` rounds them
                    flop_cap = 1 << (flop_cap - 1).bit_length()
                    out_cap = min(
                        1 << (out_cap - 1).bit_length(),
                        max(dense_tile, 1))
                    fn, kw = summa_spgemm, dict(
                        flop_capacity=flop_cap, out_capacity=out_cap,
                        merge="sort")
                elif tier == "scan":
                    fn, kw = summa_spgemm_scan, dict(
                        flop_capacity=flop_cap, out_capacity=out_cap)
                else:
                    fn, kw = summa_spgemm_mxu, dict(
                        out_capacity=out_cap, mode=mode)
                out = fn(sr, A, B, **kw)
                _publish_opnames(fn, sr, A, B, **kw)
                C, over = out if tier != "esc" else (out, 0)
                over = int(over)
                assert over <= 0, (
                    f"{tier} tier overflowed its symbolic bound by {over}"
                )
                caps = (C.capacity,)
            if sum(caps) == C.capacity:
                C = _packed(C, caps)
        with obs.span("digest"):
            nnz, hilo, counts, sums, prints = jax.device_get(
                spgemm_digest(C))
            _publish_opnames(spgemm_digest, C)
    digest = {
        "nnz": int(nnz), "sum": combine_hilo(hilo),
        "counts": counts, "sums": sums, "prints": prints,
        "tier": tier, "backend": backend,
    }
    if obs.ENABLED:
        labels = {"tier": tier, "backend": backend}
        obs.count("spgemm.job.jobs", **labels)
        obs.count("spgemm.job.products", int(products), **labels)
        obs.count("spgemm.job.nnz_out", digest["nnz"], **labels)
        obs.count("spgemm.job.windows", windows, **labels)
        obs.count("spgemm.job.windows_skipped", skipped, **labels)
        obs.count("spgemm.job.dense_flops", dense_flops, **labels)
        obs.count("spgemm.job.extract_groups", extract_groups, **labels)
        p = A.grid.pr
        obs.count("spgemm.job.stages", p, **labels)
        # the numeric phase's stage exchange, as the fullest tile pays:
        # p - 1 tiles of each operand gathered
        obs.count(
            "spgemm.job.exchange_bytes",
            (p - 1) * sum(
                4 + M.capacity * (8 + M.vals.dtype.itemsize)
                for M in (A, B)),
            **labels)
        tile_nnz = np.concatenate([
            np.asarray(t.data).ravel() for t in C.nnz.addressable_shards])
        obs.count("spgemm.job.tile_nnz_max", int(tile_nnz.max()), **labels)
        obs.count("spgemm.job.tile_nnz_min", int(tile_nnz.min()), **labels)
        obs.count("spgemm.job.pack_capacity", C.capacity, **labels)
    return C, digest
