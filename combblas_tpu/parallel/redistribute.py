"""On-device tuple redistribution (≈ SpParMat::SparseCommon).

The reference routes arbitrarily-placed (i, j, v) tuples to their owner
tiles with one MPI_Alltoallv (``SpParMat.cpp:2893-2968``) — the engine
behind matrix construction from generated edge lists
(``SpParMat(DistEdgeList&)``, SpParMat.cpp:3140-3255). The TPU-native
counterpart keeps everything in HBM: each device holds a chunk of global
tuples (e.g. straight out of the on-device R-MAT generator) and routing is
two fixed-capacity ``all_to_all`` hops over the mesh axes — first by owner
column along "c", then by owner row along "r" (classic 2D dimension-ordered
routing; the ragged Alltoallv becomes padded buckets plus an overflow
count, the static-shape contract of SURVEY §7's hard-parts list).

Capacities: ``stage_capacity`` bounds one destination bucket on one device
per hop. Tuples beyond a full bucket are dropped and COUNTED — callers
check the returned drop count (host-side, once) and retry with a larger
capacity; with ``slack`` ≈ 2x over the balanced load this is rare (R-MAT's
per-tile skew is bounded by the hub rows).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import obs
from ..ops.tuples import SpTuples
from ..semiring import Semiring
from .grid import COL_AXIS, ROW_AXIS, Grid
from .spmat import SpParMat, TILE_SPEC

Array = jax.Array


def _bucket_route(dest, rows, cols, vals, ndest, cap, pad_row, pad_col):
    """Scatter tuples into [ndest, cap] padded buckets by ``dest`` id.

    Returns (rows, cols, vals, counts, dropped): slots beyond a bucket's
    capacity are dropped (counted). Padding slots carry (pad_row, pad_col).
    """
    # position of each tuple within its destination bucket
    one = jnp.ones_like(dest)
    within = (
        jnp.zeros((ndest,), jnp.int32)
        .at[dest]
        .add(one, mode="drop")
    )
    # stable per-destination offsets via sort by dest
    order = jnp.argsort(dest, stable=True)
    dsorted = dest[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), dsorted[1:] != dsorted[:-1]]
    )
    pos_in_run = jnp.arange(dest.shape[0]) - jax.lax.associative_scan(
        jnp.maximum, jnp.where(first, jnp.arange(dest.shape[0]), 0)
    )
    slot = dsorted * cap + pos_in_run
    ok = (pos_in_run < cap) & (dsorted < ndest)
    slot = jnp.where(ok, slot, ndest * cap)
    br = jnp.full((ndest * cap,), pad_row, jnp.int32).at[slot].set(
        rows[order], mode="drop"
    )
    bc = jnp.full((ndest * cap,), pad_col, jnp.int32).at[slot].set(
        cols[order], mode="drop"
    )
    bv = jnp.zeros((ndest * cap,), vals.dtype).at[slot].set(
        vals[order], mode="drop"
    )
    dropped = jnp.sum(jnp.maximum(within - cap, 0))
    return (
        br.reshape(ndest, cap),
        bc.reshape(ndest, cap),
        bv.reshape(ndest, cap),
        dropped,
    )


@partial(
    jax.jit,
    static_argnames=("grid", "nrows", "ncols", "stage_capacity",
                     "tile_capacity", "dedup_sr"),
)
def redistribute_coo(
    grid: Grid,
    rows: Array,
    cols: Array,
    vals: Array,
    nrows: int,
    ncols: int,
    *,
    stage_capacity: int,
    tile_capacity: int,
    dedup_sr: Semiring | None = None,
) -> tuple[SpParMat, Array]:
    """Route device-resident global tuples to their owner tiles.

    rows/cols/vals: [pr, pc, chunk] — each device's arbitrary chunk of
    GLOBAL tuples (invalid slots: row >= nrows). Returns (SpParMat, total
    dropped tuple count) — check the count host-side once, after
    construction. The tile-overflow term counts DISTINCT keys when
    ``dedup_sr`` is set, so a zero count always means a complete matrix.
    """
    if obs.ENABLED:
        # trace-time only (jitted): counts (re)traces per static config
        obs.count("trace.redistribute_coo")
    lr = -(-nrows // grid.pr)
    lc = -(-ncols // grid.pc)
    pr_, pc_ = grid.pr, grid.pc

    def body(r, c, v):
        r0, c0, v0 = r[0, 0], c[0, 0], v[0, 0]
        valid = r0 < nrows
        # hop 1: route by owner COLUMN along the "c" axis
        oj = jnp.where(valid, c0 // lc, pc_)
        br, bc, bv, drop1 = _bucket_route(
            oj.astype(jnp.int32), r0, c0, v0, pc_, stage_capacity,
            jnp.int32(nrows), jnp.int32(ncols),
        )
        br = lax.all_to_all(br, COL_AXIS, split_axis=0, concat_axis=0)
        bc = lax.all_to_all(bc, COL_AXIS, split_axis=0, concat_axis=0)
        bv = lax.all_to_all(bv, COL_AXIS, split_axis=0, concat_axis=0)
        r1, c1, v1 = br.reshape(-1), bc.reshape(-1), bv.reshape(-1)
        # hop 2: route by owner ROW along the "r" axis
        valid1 = r1 < nrows
        oi = jnp.where(valid1, r1 // lr, pr_)
        br2, bc2, bv2, drop2 = _bucket_route(
            oi.astype(jnp.int32), r1, c1, v1, pr_, stage_capacity,
            jnp.int32(nrows), jnp.int32(ncols),
        )
        br2 = lax.all_to_all(br2, ROW_AXIS, split_axis=0, concat_axis=0)
        bc2 = lax.all_to_all(bc2, ROW_AXIS, split_axis=0, concat_axis=0)
        bv2 = lax.all_to_all(bv2, ROW_AXIS, split_axis=0, concat_axis=0)
        r2, c2, v2 = br2.reshape(-1), bc2.reshape(-1), bv2.reshape(-1)
        # localize to tile indices (padding maps to the sentinel)
        i = lax.axis_index(ROW_AXIS)
        j = lax.axis_index(COL_AXIS)
        ok = r2 < nrows
        lrow = jnp.where(ok, r2 - i * lr, lr).astype(jnp.int32)
        lcol = jnp.where(ok, c2 - j * lc, lc).astype(jnp.int32)
        t = SpTuples(
            rows=lrow, cols=lcol, vals=jnp.where(ok, v2, 0),
            nnz=jnp.sum(ok).astype(jnp.int32), nrows=lr, ncols=lc,
        )
        if dedup_sr is not None:
            # Exact overflow: count DISTINCT keys (duplicates collapse in
            # compact, so raw valid counts would over-report drops).
            ts = t.sort_rowmajor()
            same = (ts.rows[1:] == ts.rows[:-1]) & (ts.cols[1:] == ts.cols[:-1])
            is_new = ts.valid_mask() & ~jnp.concatenate(
                [jnp.zeros((1,), bool), same]
            )
            distinct = jnp.sum(is_new).astype(jnp.int32)
            drop3 = jnp.maximum(distinct - tile_capacity, 0)
            t = t.compact(dedup_sr, capacity=tile_capacity)
        else:
            nvalid = jnp.sum(ok).astype(jnp.int32)
            drop3 = jnp.maximum(nvalid - tile_capacity, 0)
            t = t._select(ok).with_capacity(tile_capacity)
        dropped = lax.psum(
            lax.psum(drop1 + drop2 + drop3, ROW_AXIS), COL_AXIS
        )
        return SpParMat._pack_tile(t) + (dropped[None],)

    r, c, v, n, dropped = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 3,
        # drop count REPLICATED (P()): every process must be able to read
        # it whole for the host-side retry decision under multi-process
        out_specs=(TILE_SPEC,) * 4 + (P(),),
        check_vma=False,
    )(rows, cols, vals)
    mat = SpParMat(
        rows=r, cols=c, vals=v, nnz=n, nrows=int(nrows), ncols=int(ncols),
        grid=grid,
    )
    return mat, dropped[0]


def from_device_coo(
    grid: Grid,
    rows: Array,
    cols: Array,
    vals: Array,
    nrows: int,
    ncols: int,
    *,
    slack: float = 2.0,
    max_retries: int = 3,
    dedup_sr: Semiring | None = None,
    defer_drop_check: bool = False,
):
    """Convenience wrapper: size capacities from the chunk shape, route,
    and on drops retry with doubled capacities (skewed inputs — R-MAT hub
    columns — routinely exceed the balanced-load estimate). Raises only
    after ``max_retries`` doublings.

    ``defer_drop_check=True`` returns ``(mat, dropped)`` with the drop
    count as a DEVICE scalar and performs NO retries — for timed pipelines,
    where the retry loop's readback would put a host sync inside the timed
    section; callers verify ``int(dropped) == 0`` after it and rerun with
    bigger ``slack`` if not."""
    chunk = rows.shape[-1]
    # hop 2's buckets aggregate up to pc incoming hop-1 buckets, so size the
    # shared stage capacity from the larger of the two hops' balanced loads.
    per_dest1 = -(-chunk // grid.pc)
    per_dest2 = -(-chunk // grid.pr)
    stage_cap = 1 << max(
        int(np.ceil(np.log2(max(max(per_dest1, per_dest2) * slack, 1)))), 0
    )
    # total tuples = chunk * ndev over ndev tiles → ~chunk per tile.
    tile_cap = 1 << max(int(np.ceil(np.log2(max(chunk * slack, 1)))), 0)
    from .spgemm import host_value

    if defer_drop_check:
        if obs.ENABLED:
            obs.gauge("redistribute.stage_capacity", stage_cap)
            obs.gauge("redistribute.tile_capacity", tile_cap)
        mat, dropped = redistribute_coo(
            grid, rows, cols, vals, nrows, ncols,
            stage_capacity=stage_cap, tile_capacity=tile_cap,
            dedup_sr=dedup_sr,
        )
        return mat, dropped

    nd = 0
    with obs.span("redistribute", chunk=int(chunk)):
        for attempt in range(max_retries + 1):
            mat, dropped = redistribute_coo(
                grid, rows, cols, vals, nrows, ncols,
                stage_capacity=stage_cap, tile_capacity=tile_cap,
                dedup_sr=dedup_sr,
            )
            nd = int(host_value(dropped))
            if obs.ENABLED:
                # the actual drop count per attempt — zero on success, so
                # the counter reads as total tuples ever bounced
                obs.count("redistribute.dropped", nd)
                obs.span_event(
                    "route", attempt=attempt, dropped=nd,
                    stage_capacity=stage_cap, tile_capacity=tile_cap,
                )
            if nd == 0:
                return mat
            if obs.ENABLED:
                obs.count("redistribute.retries")
            stage_cap *= 2
            tile_cap *= 2
    raise ValueError(
        f"redistribute still dropped {nd} tuples after {max_retries} "
        "capacity doublings; call redistribute_coo with explicit capacities"
    )
