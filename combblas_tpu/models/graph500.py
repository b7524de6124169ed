"""Graph500 kernel 1 — distributed graph construction, composed on device.

The reference's Graph500 driver builds the matrix distributed
(``TopDownBFS.cpp:270-370`` calling ``DistEdgeList::GenGraph500Data``,
``PermEdges``/``RenameVertices`` from ``DistEdgeList.cpp``, then the
``SpParMat`` Graph500 constructor ``SpParMat.cpp:3140-3441``: Alltoallv to
owner processes → dedup → Symmetricize → RemoveLoops → random-permutation
relabel → SpRef of non-isolated vertices → OptimizeForGraph500).  The
TPU-native composition below runs every distributed stage as XLA programs
over the grid mesh:

  generate (device threefry R-MAT, ``utils/rmat.py``)
  → symmetricize + de-loop (mask arithmetic on the edge list)
  → route to owner tiles (``redistribute_coo`` two-hop all_to_all) + dedup
  → optional extra random relabel (``permute_vertices`` — the
    PermEdges/RenameVertices analog, also used for file-loaded graphs)
  → isolated-vertex compression (rank-by-degree relabel: the static-shape
    analog of the reference's shrinking SpRef — non-isolated vertices are
    renumbered into a dense prefix [0, nkeep), isolated ones to the tail;
    the matrix keeps its static n, the tail rows/cols are empty)

Everything except capacity sizing (trace-time constants) and the
drop-retry check stays on device.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import obs
from ..parallel.grid import COL_AXIS, ROW_AXIS, Grid
from ..parallel.redistribute import from_device_coo
from ..parallel.spmat import TILE_SPEC, SpParMat
from ..parallel.vec import DistVec
from ..semiring import PLUS_TIMES, SELECT2ND_MAX


def permute_vertices(A: SpParMat, p: DistVec, *, slack: float = 2.0,
                     max_retries: int = 3) -> SpParMat:
    """Symmetric relabel: A'[p[i], p[j]] = A[i, j].

    The distributed analog of ``DistEdgeList::RenameVertices`` /
    ``PermEdges`` (DistEdgeList.cpp) and of the driver's random-permutation
    SpRef — the load-balancing relabel the reference applies to
    file-loaded graphs before BFS.  ``p`` is a permutation of
    [0, nrows) (e.g. ``DistVec.randperm``); requires a square matrix.

    Each tile maps its local tuples to permuted GLOBAL coordinates via the
    row-/col-aligned slices of ``p``, then one two-hop all_to_all routes
    them to their new owner tiles (capacity-doubling retry like
    ``from_device_coo`` — permutations preserve nnz but can skew tiles).
    """
    assert A.nrows == A.ncols, "vertex permutation needs a square matrix"
    grid = A.grid
    n = A.nrows
    lr, lc = A.local_rows, A.local_cols
    prow = p.realign("row").blocks  # [pr, lr] new id for each local row
    pcol = p.realign("col").blocks  # [pc, lc] new id for each local col

    def to_global(rows, cols, vals, nnz, pr_blk, pc_blk):
        valid = rows[0, 0] < lr
        pr_pad = jnp.concatenate([pr_blk[0], jnp.full((1,), n, jnp.int32)])
        pc_pad = jnp.concatenate([pc_blk[0], jnp.full((1,), n, jnp.int32)])
        gr = pr_pad[jnp.minimum(rows[0, 0], lr)]
        gc = pc_pad[jnp.minimum(cols[0, 0], lc)]
        gr = jnp.where(valid, gr, n)
        gc = jnp.where(valid, gc, n)
        return gr[None, None], gc[None, None], vals

    gr, gc, gv = jax.shard_map(
        to_global,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 4 + (P(ROW_AXIS), P(COL_AXIS)),
        out_specs=(TILE_SPEC,) * 3,
        check_vma=False,
    )(A.rows, A.cols, A.vals, A.nnz, prow, pcol)

    return from_device_coo(
        grid, gr, gc, gv, n, n, slack=slack, max_retries=max_retries
    )


def isolated_compression_perm(A: SpParMat) -> tuple[DistVec, jax.Array]:
    """Permutation renumbering non-isolated vertices into a dense prefix.

    Returns (p, nkeep): ``p[v]`` is v's new id — vertices with degree > 0
    (counting either direction; A is assumed symmetric here, matching the
    Graph500 pipeline) get ranks [0, nkeep) in original order, isolated
    vertices get [nkeep, n).  The static-shape analog of the reference's
    shrinking ``SpRef`` of non-isolated vertices (SpParMat.cpp:3140-3441
    pipeline): instead of shrinking the matrix (dynamic shape), relabel so
    the live vertices are a prefix and report ``nkeep``.
    """
    deg = A.nnz_per_column()  # col-aligned [pc, lc]
    grid = A.grid
    n = A.ncols

    def body(dblk):
        local = dblk[0]  # [lc]
        has = (local > 0).astype(jnp.int32)
        # global exclusive scan: local prefix + offset of preceding blocks
        local_cum = jnp.cumsum(has) - has  # exclusive within block
        tot = jnp.sum(has)
        j = lax.axis_index(COL_AXIS)
        totals = lax.all_gather(tot, COL_AXIS)  # [pc]
        before = jnp.sum(jnp.where(jnp.arange(grid.pc) < j, totals, 0))
        nkeep = jnp.sum(totals)
        # isolated ranks: same construction over the complement
        iso = 1 - has
        iso_cum = jnp.cumsum(iso) - iso
        iso_tot = jnp.sum(iso)
        iso_totals = lax.all_gather(iso_tot, COL_AXIS)
        iso_before = jnp.sum(
            jnp.where(jnp.arange(grid.pc) < j, iso_totals, 0)
        )
        rank = jnp.where(
            has == 1,
            before + local_cum,
            nkeep + iso_before + iso_cum,
        ).astype(jnp.int32)
        return rank[None], nkeep[None]

    blocks, nkeep = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(P(COL_AXIS),),
        out_specs=(P(COL_AXIS), P()),
        check_vma=False,
    )(deg.blocks)
    p = DistVec(blocks=blocks, length=n, align="col", grid=grid)
    return p, nkeep[0]


def kernel1_device(
    grid: Grid,
    scale: int,
    edgefactor: int,
    key,
    *,
    extra_relabel: bool = False,
    compress_isolated: bool = True,
    slack: float = 2.0,
):
    """Graph500 kernel 1, composed from distributed device stages.

    Returns ``(A, degrees, nkeep, timings)``: the symmetric dedup'd
    adjacency SpParMat (non-isolated vertices renumbered to a dense prefix
    when ``compress_isolated``), its row-degree DistVec, the device scalar
    count of non-isolated vertices, and a stage→seconds dict (wall-clock,
    synchronized per stage with ``block_until_ready`` — indicative on CPU,
    construction-grade on chip where it is timed in its own process).
    """
    from ..utils.rmat import rmat_edges

    timings: dict[str, float] = {}
    n = 1 << scale
    ndev = grid.pr * grid.pc

    t0 = time.perf_counter()
    with obs.span("k1.generate", scale=scale):
        # generate (spec's vertex scramble included), symmetricize, de-loop
        src, dst = rmat_edges(key, scale, edgefactor * n)
        rows = jnp.concatenate([src, dst])
        cols = jnp.concatenate([dst, src])
        keep = rows != cols
        rows = jnp.where(keep, rows, n).astype(jnp.int32)
        cols = jnp.where(keep, cols, n).astype(jnp.int32)
        # shard the flat edge list into per-device chunks for routing
        total = rows.shape[0]
        chunk = -(-total // ndev)
        pad = chunk * ndev - total
        if pad:
            rows = jnp.concatenate([rows, jnp.full((pad,), n, jnp.int32)])
            cols = jnp.concatenate([cols, jnp.full((pad,), n, jnp.int32)])
        shape = (grid.pr, grid.pc, chunk)
        rows = jax.device_put(rows.reshape(shape), grid.tile_sharding())
        cols = jax.device_put(cols.reshape(shape), grid.tile_sharding())
        jax.block_until_ready((rows, cols))
    timings["generate_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    with obs.span("k1.route_dedup"):
        vals = jnp.ones(shape, jnp.float32)
        # defer_drop_check: the capacity-retry readback would put a host
        # sync inside the caller's timed section; the drop count rides
        # along as a device scalar (timings["dropped_dev"]) for the
        # caller to verify AFTER it.
        A, dropped = from_device_coo(
            grid, rows, cols, vals, n, n, slack=slack,
            dedup_sr=SELECT2ND_MAX, defer_drop_check=True,
        )
        jax.block_until_ready(A.vals)
    timings["route_dedup_s"] = time.perf_counter() - t0
    timings["dropped_dev"] = dropped

    if extra_relabel:
        t0 = time.perf_counter()
        with obs.span("k1.relabel"):
            p = DistVec.randperm(grid, n, jax.random.fold_in(key, 1))
            A = permute_vertices(A, p)
            jax.block_until_ready(A.vals)
        timings["relabel_s"] = time.perf_counter() - t0

    nkeep = jnp.asarray(n, jnp.int32)
    if compress_isolated:
        t0 = time.perf_counter()
        with obs.span("k1.compress_isolated"):
            p, nkeep = isolated_compression_perm(A)
            A = permute_vertices(A, p)
            jax.block_until_ready(A.vals)
        timings["compress_isolated_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    with obs.span("k1.degree"):
        degrees = A.reduce(
            PLUS_TIMES, "row", map_fn=lambda v: (v != 0).astype(v.dtype)
        )
        jax.block_until_ready(degrees.blocks)
    timings["degree_s"] = time.perf_counter() - t0
    if obs.ENABLED:
        # kernel-1 stage times as histograms (the per-stage TIMING table)
        for k, v in timings.items():
            if isinstance(v, float):
                obs.observe("k1." + k, v)
    return A, degrees, nkeep, timings
