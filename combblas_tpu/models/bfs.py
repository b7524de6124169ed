"""Graph500 top-down BFS (≈ Applications/TopDownBFS.cpp).

The reference iterates ``fringe = SpMV(A, fringe, optbuf)`` with a
select-max semiring, prunes discovered vertices with ``EWiseMult``, and sets
parents (``TopDownBFS.cpp:437-444``; semiring ``SelectMaxSRing``
Semirings.h:166).  The frontier there is a ``FullyDistSpVec`` because on CPU
clusters touching only active vertices is the whole game.

On TPU the frontier is a *dense* distributed vector of parent candidates
(-1 = inactive): every step is one masked semiring SpMV + elementwise
updates, with zero dynamic shapes — the compiled program is identical every
iteration, which is what XLA wants.  This is the same observation that makes
the reference's *bottom-up* phase (``BFSFriends.h:457-560``) dense: we simply
run the dense formulation in both regimes.  TEPS is unchanged: inactive
lanes carry the additive identity through the same ALU ops the active lanes
use.

The sparse-frontier SpMSpV path still exists (``parallel/spmv.py`` +
``ops/spmv.spmspv``) for API parity and for workloads with tiny frontiers.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import PAD_ROOT
from .. import obs
from ..semiring import PLUS_TIMES, SELECT2ND_MAX
from ..parallel.spmat import SpParMat, ones_i32
from ..parallel.spmv import dist_spmspv_masked, dist_spmv_masked
from ..parallel.vec import DistVec

#: The ``jax.named_scope`` names of the two batch programs the served
#: path and ``bfs_batch_compact`` run (``_bfs_batch_impl``,
#: ``_bfs_batch_compact_program``), outermost first; ``ell.bucket<i>`` is
#: one scope per degree class.  Trace-time metadata only: the device
#: trace's per-scope and per-level times are read by these names
#: (docs/observability.md "Named scopes"), so a rename is a change of
#: yardstick.  ``bfs.parents`` exists in the compact program only,
#: ``bfs.push`` (a level as a walk of its frontier's columns, inside the
#: loop: the fit test every level, and under the level's ``cond`` the
#: walk's ``push.columns``, ``push.lay``, ``push.walk``, ``push.scatter``
#: and its ``ell.reduce``) in a program handed the CSC companion only:
#: the served plan.
BFS_SCOPES = (
    "bfs.init",
    "bfs.push",
    "bfs.level",  # the whole while loop; one iteration = one level
    # inside it: gather, fold, scatter_rows; the count that lets a masked
    # sweep skip the class is the scope's own
    "ell.bucket<i>",
    "gather",
    "fold",
    "scatter_rows",
    "ell.reduce",  # the COL_AXIS fold of the tile partials
    "bfs.update",
    "vec.realign",
    "bfs.active",
    "bfs.parents",
)


def _global_ids(grid, nblocks, block_len, length, align):
    gids = jnp.arange(nblocks * block_len, dtype=jnp.int32).reshape(
        nblocks, block_len
    )
    return jnp.where(gids < length, gids, -1)


@partial(jax.jit, static_argnames=("max_iters", "sr"))
def bfs(
    A: SpParMat,
    source,
    max_iters: int | None = None,
    sr: "Semiring" = SELECT2ND_MAX,
):
    """Level-synchronous BFS from ``source`` over a select-style semiring
    (default SELECT2ND_MAX — structural; pass a value-aware semiring like
    ``semantic.FILTERED_SELECT2ND_MAX`` for on-the-fly edge filtering).

    A is interpreted as: entry (i, j) ≠ 0 means edge j → i (gather from
    in-neighbors, matching the reference's SpMV orientation). Symmetrize for
    undirected graphs.

    Returns (parents, levels, num_iters): row-aligned DistVecs of int32;
    undiscovered vertices hold -1.
    """
    grid = A.grid
    n = A.nrows
    pr_, lr = grid.pr, grid.local_rows(n)
    pc_, lc = grid.pc, grid.local_cols(A.ncols)
    iters = max_iters if max_iters is not None else n

    row_gids = _global_ids(grid, pr_, lr, n, "row")
    col_gids = _global_ids(grid, pc_, lc, A.ncols, "col")

    parents0 = jnp.where(row_gids == source, source, -1).astype(jnp.int32)
    levels0 = jnp.where(row_gids == source, 0, -1).astype(jnp.int32)
    x0 = jnp.where(col_gids == source, source, -1).astype(jnp.int32)

    def mk_row(blocks):
        return DistVec(blocks=blocks, length=n, align="row", grid=grid)

    def mk_col(blocks):
        return DistVec(blocks=blocks, length=A.ncols, align="col", grid=grid)

    def cond(state):
        _, _, x, level, active = state
        return active & (level < iters)

    def step(state):
        parents, levels, x, level, _ = state
        unvisited = mk_row(parents < 0)
        y = dist_spmv_masked(sr, A, mk_col(x), unvisited)
        new = (y.blocks >= 0) & (parents < 0) & (row_gids >= 0)
        parents = jnp.where(new, y.blocks, parents)
        levels = jnp.where(new, level + 1, levels)
        frontier_row = mk_row(jnp.where(new, row_gids, -1))
        x_next = frontier_row.realign("col").blocks
        active = jnp.any(new)
        return parents, levels, x_next, level + 1, active

    parents, levels, _, niter, _ = jax.lax.while_loop(
        cond, step, (parents0, levels0, x0, jnp.int32(0), jnp.bool_(True))
    )
    return mk_row(parents), mk_row(levels), niter


@partial(jax.jit, static_argnames=("frontier_capacity", "exp_capacity"))
def _diropt_topdown_step(
    A, parents, levels, x, row_gids, level, frontier_capacity, exp_capacity
):
    """One sparse-frontier (top-down) level. x is the col-aligned dense
    candidate vector (-1 = inactive)."""
    grid = A.grid
    n = A.nrows
    unvisited = DistVec(
        blocks=parents < 0, length=n, align="row", grid=grid
    )
    xv = DistVec(blocks=x, length=A.ncols, align="col", grid=grid)
    xact = DistVec(blocks=x >= 0, length=A.ncols, align="col", grid=grid)
    y = dist_spmspv_masked(
        SELECT2ND_MAX, A, xv, xact, unvisited,
        frontier_capacity=frontier_capacity, exp_capacity=exp_capacity,
    )
    return _diropt_update(A, parents, levels, y, row_gids, level)


@jax.jit
def _diropt_bottomup_step(A, parents, levels, x, row_gids, level):
    """One dense (bottom-up regime) level: every unvisited vertex probes all
    its neighbors in one masked SpMV — the dense formulation that plays the
    role of the reference's BottomUpStep carousel (``BFSFriends.h:457-560``;
    the ring rotation is XLA's own ICI all-reduce lowering of the fold)."""
    grid = A.grid
    n = A.nrows
    unvisited = DistVec(blocks=parents < 0, length=n, align="row", grid=grid)
    xv = DistVec(blocks=x, length=A.ncols, align="col", grid=grid)
    y = dist_spmv_masked(SELECT2ND_MAX, A, xv, unvisited)
    return _diropt_update(A, parents, levels, y, row_gids, level)


def _diropt_update(A, parents, levels, y, row_gids, level):
    new = (y.blocks >= 0) & (parents < 0) & (row_gids >= 0)
    parents = jnp.where(new, y.blocks, parents)
    levels = jnp.where(new, level + 1, levels)
    frontier_row = DistVec(
        blocks=jnp.where(new, row_gids, -1), length=A.nrows, align="row",
        grid=A.grid,
    )
    x_next = frontier_row.realign("col").blocks
    nnew = jnp.sum(new).astype(jnp.int32)
    return parents, levels, x_next, nnew


@jax.jit
def _frontier_stats(x, deg_blocks):
    """(frontier vertex count, frontier out-edge count) from the col-aligned
    candidate vector.

    The edge count accumulates in float32: int32 would wrap for hub-heavy
    frontiers at Graph500 scale and silently corrupt the regime switch. The
    caller compensates for float32 rounding with a 1% comparison margin.
    """
    act = x >= 0
    cnt = jnp.sum(act)
    edges = jnp.sum(jnp.where(act, deg_blocks, 0).astype(jnp.float32))
    return cnt, edges


@partial(
    jax.jit,
    static_argnames=("frontier_capacity", "exp_capacity", "max_iters"),
)
def bfs_diropt(
    A: SpParMat,
    source,
    *,
    frontier_capacity: int,
    exp_capacity: int,
    max_iters: int | None = None,
):
    """Direction-optimizing BFS (≈ Applications/DirOptBFS.cpp, Beamer),
    fully on device.

    The per-level regime switch is a ``lax.cond`` on frontier statistics
    INSIDE the while_loop — both regimes compile once and zero
    device-to-host readbacks happen during the search (the round-1 host
    switch degraded the round-5 machine's launch path via its per-level
    ``int(cnt)`` readbacks; ROADMAP D14). Top-down runs the
    budgeted sparse-frontier kernel (work ∝ the static budgets); bottom-up
    runs the dense masked SpMV (work ∝ tile nnz, the regime where the
    reference's carousel operates, ``DirOptBFS.cpp:374-424``).

    The caller chooses the static budgets; the switch takes top-down when
    the frontier fits BOTH budgets with the same 1% float32 margin the
    host version used.

    Returns (parents, levels, num_iters) like ``bfs``.
    """
    grid = A.grid
    n = A.nrows
    pr_, lr = grid.pr, grid.local_rows(n)
    pc_, lc = grid.pc, grid.local_cols(A.ncols)
    iters = max_iters if max_iters is not None else n

    row_gids = _global_ids(grid, pr_, lr, n, "row")
    col_gids = _global_ids(grid, pc_, lc, A.ncols, "col")
    parents0 = jnp.where(row_gids == source, jnp.int32(source), -1)
    levels0 = jnp.where(row_gids == source, 0, -1).astype(jnp.int32)
    x0 = jnp.where(col_gids == source, jnp.int32(source), -1)

    # out-degree per column (structural), for the edge-budget check
    deg = A.reduce(PLUS_TIMES, "rows", map_fn=ones_i32).blocks

    def cond(state):
        _, _, _, level, active = state
        return active & (level < iters)

    def step(state):
        parents, levels, x, level, _ = state
        cnt, edges = _frontier_stats(x, deg)
        use_topdown = (cnt <= frontier_capacity) & (
            edges <= 0.99 * exp_capacity
        )
        parents, levels, x, nnew = jax.lax.cond(
            use_topdown,
            lambda a: _diropt_topdown_step(
                A, a[0], a[1], a[2], row_gids, a[3],
                frontier_capacity, exp_capacity,
            ),
            lambda a: _diropt_bottomup_step(
                A, a[0], a[1], a[2], row_gids, a[3]
            ),
            (parents, levels, x, level),
        )
        return parents, levels, x, level + 1, nnew > 0

    parents, levels, _, niter, _ = jax.lax.while_loop(
        cond, step, (parents0, levels0, x0, jnp.int32(0), jnp.bool_(True))
    )
    mk = lambda b: DistVec(blocks=b, length=n, align="row", grid=grid)
    return mk(parents), mk(levels), niter


def bfs_diropt_auto(A: SpParMat, source, max_iters: int | None = None):
    """``bfs_diropt`` with the round-1 default budget heuristics
    (host-side, static: lc/8 frontier slots, nnz-capacity/8 edge slots)."""
    lc = A.grid.local_cols(A.ncols)
    cap = A.capacity
    fc = min(max(64, lc // 8 + 1), lc)
    ec = min(max(256, cap // 8 + 1), cap)
    return bfs_diropt(
        A, source, frontier_capacity=fc, exp_capacity=ec,
        max_iters=max_iters,
    )


def traversed_edges(A: SpParMat, parents: DistVec) -> jax.Array:
    """Graph500 kernel-2 edge count: edges with a discovered endpoint / 2.

    Matches the TEPS accounting of ``TopDownBFS.cpp:448-465`` for
    symmetrized graphs (each undirected edge stored twice).
    """
    deg = A.reduce(PLUS_TIMES, axis="cols", map_fn=ones_i32)
    disc = parents.realign("row").blocks >= 0
    return jnp.sum(jnp.where(disc, deg.blocks, 0)) // 2


def validate_bfs_tree(A_dense, source, parents, levels) -> list[str]:
    """Host-side BFS tree validation (Graph500 verify.c-style checks).

    Returns a list of violation strings (empty = valid).
    """
    import numpy as np

    A_dense = np.asarray(A_dense)
    p = np.asarray(parents)
    lv = np.asarray(levels)
    n = A_dense.shape[0]
    errs = []
    if p[source] != source or lv[source] != 0:
        errs.append("source not its own parent at level 0")
    for v in range(n):
        if v == source or p[v] < 0:
            continue
        if not A_dense[v, p[v]]:
            errs.append(f"tree edge ({p[v]},{v}) not in graph")
        if lv[v] != lv[p[v]] + 1:
            errs.append(f"level[{v}]={lv[v]} != level[parent]+1={lv[p[v]] + 1}")
    # reachability: discovered set must equal BFS-reachable set
    from collections import deque

    seen = {source}
    q = deque([source])
    while q:
        u = q.popleft()
        for w in np.nonzero(A_dense[:, u])[0]:
            if w not in seen:
                seen.add(w)
                q.append(w)
    disc = {int(v) for v in range(n) if p[v] >= 0}
    if disc != seen:
        errs.append(f"discovered {len(disc)} != reachable {len(seen)}")
    return errs


@jax.jit
def validate_bfs_device(E, parents, levels):
    """DEVICE-side Graph500 tree validation for chip-scale runs
    (``graph500-1.2 verify.c`` intent; the host ``validate_bfs_tree`` is
    O(n·m) Python and unusable at benchmark scales).

    ``E``: EllParMat adjacency; ``parents``/``levels``: row-aligned
    DistMultiVec int32 [n, W] (levels -1 = undiscovered). Checks, per
    lane, with a handful of bucket-sweep passes (each ~nnz per-slot ops):

      v1  roots: exactly one self-parent vertex at level 0 per lane;
      v2  level step: level[v] == level[parent[v]] + 1 for discovered
          non-root v (and parent discovered);
      v3  tree-edge membership: edge (parent[v], v) exists in the graph;
      v4  edge consistency: no graph edge joins a discovered vertex to an
          undiscovered one, and discovered endpoints' levels differ <= 1
          (levels are true BFS distances & discovery is closed).

    Returns a [4, W] int32 violation-count matrix (all zeros = valid).
    Run AFTER the timed section — its readback poisons later launches.
    """
    from jax.sharding import PartitionSpec as P

    from ..parallel.grid import COL_AXIS, ROW_AXIS
    from ..parallel.spmat import TILE_SPEC

    grid = E.grid
    n = E.nrows
    lr, lc = E.local_rows, E.local_cols
    nb = len(E.buckets)
    lcol = levels.realign("col")

    def body(prow_b, lrow_b, lcol_b, *flat):
        buckets = [
            tuple(a[0, 0] for a in flat[3 * i : 3 * i + 3]) for i in range(nb)
        ]
        prow, lrow = prow_b[0], lrow_b[0]  # [lr, W]
        lc_b = lcol_b[0]  # [lc, W]
        W = prow.shape[1]
        i = jax.lax.axis_index(ROW_AXIS)
        j = jax.lax.axis_index(COL_AXIS)
        row_g = jnp.arange(lr, dtype=jnp.int32) + i * lr  # global row ids
        rvalid = row_g < n

        # v1: root accounting (root = self-parent at level 0)
        is_root = (prow == row_g[:, None]) & (lrow == 0) & rvalid[:, None]
        nroots = jax.lax.psum(
            jnp.sum(is_root.astype(jnp.int32), axis=0), ROW_AXIS
        )
        v1 = jnp.abs(nroots - 1)

        # full per-lane level table for parent lookups (validation W is
        # small; all_gather of [lc, W] over "c" = the global vector)
        lvl_full = jax.lax.all_gather(lc_b, COL_AXIS).reshape(-1, W)[:n]
        disc = (lrow >= 0) & rvalid[:, None]
        nonroot = disc & ~is_root
        pidx = jnp.clip(prow, 0, n - 1)
        lane = jnp.arange(W, dtype=jnp.int32)[None, :]
        lp = lvl_full[pidx, lane]  # lp[v, w] = level[parent[v, w], w]
        v2 = jax.lax.psum(
            jnp.sum(
                (nonroot & ((lp < 0) | (lrow != lp + 1))).astype(jnp.int32),
                axis=0,
            ),
            ROW_AXIS,
        )

        # v3 + v4: one sweep over the ELL buckets
        lpad = jnp.concatenate(
            [lc_b, jnp.full((1, W), -1, lc_b.dtype)]
        )  # [lc+1, W]
        tree_found = jnp.zeros((lr, W), bool)
        v4 = jnp.zeros((W,), jnp.int32)
        for bc0, _bv0, br0 in buckets:  # the shard-LOCAL tile slices
            rowok = br0 < lr  # padded bucket rows are inert
            slot_ok = (bc0 < lc) & rowok[:, None]  # [nbk, kb]
            colg = jnp.where(slot_ok, bc0 + j * lc, n)
            g = lpad[jnp.minimum(bc0, lc)]  # [nbk, kb, W] neighbor levels
            rl = lrow[jnp.minimum(br0, lr - 1)]  # [nbk, W] row levels
            rd = rl >= 0
            nd = g >= 0
            bad_cross = slot_ok[..., None] & (rd[:, None, :] != nd)
            bad_far = (
                slot_ok[..., None]
                & rd[:, None, :] & nd
                & (jnp.abs(g - rl[:, None, :]) > 1)
            )
            v4 = v4 + jnp.sum(
                (bad_cross | bad_far).astype(jnp.int32), axis=(0, 1)
            )
            pv = prow[jnp.minimum(br0, lr - 1)]  # [nbk, W] parent ids
            match = slot_ok[..., None] & (colg[..., None] == pv[:, None, :])
            hit = jnp.any(match, axis=1) & rowok[:, None]  # [nbk, W]
            tree_found = tree_found.at[jnp.minimum(br0, lr - 1)].max(hit)
        # a row's full adjacency may span several grid columns
        tree_found = jax.lax.pmax(tree_found, COL_AXIS)
        v4 = jax.lax.psum(jax.lax.psum(v4, COL_AXIS), ROW_AXIS)
        v3 = jax.lax.psum(
            jnp.sum((nonroot & ~tree_found).astype(jnp.int32), axis=0),
            ROW_AXIS,
        )
        return jnp.stack([v1, v2, v3, v4])[None]

    flat_args = [a for b in E.buckets for a in b]
    out = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(P(ROW_AXIS), P(ROW_AXIS), P(COL_AXIS))
        + (TILE_SPEC,) * (3 * nb),
        out_specs=P(None),
        check_vma=False,
    )(
        parents.realign("row").blocks, levels.realign("row").blocks,
        lcol.blocks, *flat_args,
    )
    return out[0]


def bfs_batch(
    A,
    sources,
    max_iters: int | None = None,
    track_levels: bool = True,
):
    """Eager wrapper over ``_bfs_batch_impl`` (plain-outputs law: a
    dataclass-wrapped jit output tripled the batch child's wall time in
    the round-5 A/B on a machine that is gone; ROADMAP D14).  A library
    caller holds no CSC companion, so this is the all-pull program;
    the served plan takes its thin levels as pushes
    (``_bfs_batch_tallied``): same answer."""
    from ..parallel.vec import DistMultiVec

    p, l, niter = _bfs_batch_impl(
        A, sources, max_iters=max_iters, track_levels=track_levels,
    )
    mk = lambda b: DistMultiVec(
        blocks=b, length=A.nrows, align="row", grid=A.grid
    )
    return mk(p), mk(l), niter


@partial(jax.jit, static_argnames=("max_iters", "track_levels"))
def _bfs_batch_impl(
    A,
    sources,
    max_iters: int | None = None,
    track_levels: bool = True,
):
    """Multi-source batched BFS: W independent BFS trees in ONE program.

    Graph500 runs 64 search keys (the reference loops them host-side,
    ``TopDownBFS.cpp:437-444``); on TPU the whole batch advances together as
    a [n, W] frontier matrix — SURVEY §2.3 strategy 7 (BetwCent's
    frontier-as-matrix) applied to BFS itself: the whole batch is one
    launch, and one gathered index serves all W lanes (a level gathers
    one membership word a column, ``_bfs_batch_tallied``).  A row's
    parent is its largest in-frontier in-neighbour id: the reference's
    ``SelectMaxSRing`` (``semiring.SELECT2ND_MAX``), the one semiring a
    batched level has.

    ``sources``: int32 [W]. Returns (parents [pr, lr, W] int32 blocks,
    levels blocks, num_iters) — PLAIN ARRAYS (the eager wrapper above
    rebuilds the DistMultiVecs); num_iters is the MAX level over the
    batch (lanes that finish early idle through the remaining levels with
    no semantic effect; dense-regime level cost is frontier-independent).
    ``track_levels=False`` drops the level array from the loop carry,
    saving one [n, W] int32 buffer (it raised the feasible batch width
    from 256 toward 384 at scale 20 — W=512 still exceeds this chip's
    16G HBM; round-2 width sweep). Levels are then
    returned as a discovery indicator (0 discovered / -1 not).
    """
    return _bfs_batch_tallied(A, sources, max_iters, track_levels)[:3]


#: Edge slots a tile has for the columns of one level's frontiers, all
#: W lanes' together: a level that fits is walked (``ell_frontier_push``),
#: any other is swept (``_bfs_batch_tallied``).  Host counts of 16-root
#: batches (``chipbench.graph.draw_roots``, three seeds each, the lanes'
#: frontiers added up) on the two graph laws the benchmark has, PR 52.
#: The random geometric graph (DIMACS10 ``rgg_n_2_20``: degree 13,
#: 799-854 levels a batch): a level's frontiers hold 251-312 K edges at
#: the median and 477 K, 506 K, 546 K at most (23.6 K columns at the
#: median, 41.1 K at most), so 2^19 would leave up to 78 levels of a
#: batch to the sweep and 2^20 leaves none (``rgg_n_2_19``, the size
#: the benchmark's cell runs at: 175-204 K at the median, 355 K at
#: most, 561-630 levels a batch).  Graph500 scale 20 (R-MAT,
#: 7-8 levels a batch): 145-4,105 edges at level 0 (the roots' own: 442
#: at the median of a million draws, past 2^17 in none, ISSUE 29), 318 K,
#: 849 K and 6.2 M at level 1, 14.6-348 M at levels 2 to 4, 64-67 K at
#: level 5, about 200 at level 6: under 2^20 the first level, the second
#: in two batches of three and the last two or three are walked, and no
#: power of two short of 2^24 would add one.  What a walked level costs
#: follows its frontier, not this number, which sizes three arrays of one
#: word a slot: a level over it costs a sweep, as every level did.
#: Static: it sizes the walk.
PUSH_EDGE_CAPACITY = 1 << 20

#: ... and never more than this share of the slots a sweep of the matrix
#: gathers: a walked edge is an indexed read and an indexed write, a
#: swept slot one read in a dense fold, so past an eighth of the matrix
#: the sweep is the cheaper level.  It binds on a small matrix only
#: (under 2^23 slots: neither benchmark graph), where without it every
#: level of every search would fit and nothing would ever be swept.
PUSH_SLOT_SHARE = 1 / 8


def push_capacity(E) -> int:
    """Edges a tile's frontier columns may hold for a level of a search
    over ``E`` to be walked: ``PUSH_EDGE_CAPACITY``, or
    ``PUSH_SLOT_SHARE`` of a tile's ELL slots where that is less.
    Static (from shapes): it sizes the walk."""
    from ..parallel.ellmat import class_slots

    return max(1, min(
        PUSH_EDGE_CAPACITY, int(PUSH_SLOT_SHARE * sum(class_slots(E)))))


#: What the device chose for level 0, a scalar of the ``PushReport`` a
#: program handed the companion returns: ``serve.bfs.push{outcome}``.
PUSH_OUTCOMES = ("taken", "over_budget", "stale")

#: ``serve.bfs.levels{mode}``: how a level of a served batch was run.
LEVEL_MODES = ("push", "pull")


class PushReport(NamedTuple):
    """What a program handed the CSC companion says of its pushes."""

    outcome: jax.Array  # int32 scalar, level 0's, into ``PUSH_OUTCOMES``
    levels: jax.Array  # int32 scalar: levels taken as a push
    edges: jax.Array  # int32[pr, pc]: edges each tile's pushes walked
    #: int32[pr, pc]: passes each tile's pushes made over a trip of
    #: ``ellmat.PUSH_SLOT_CHUNK`` slots to scatter them, a lane of every
    #: slot a pass; times the trip over ``edges``, scattered slots an edge
    passes: jax.Array


#: What a served BFS level gathers from (``serve.warmup``'s ``payload``
#: attribute): the frontier as membership bits, one int32 word a column
#: for every 32 lanes (``ellmat.pack_lanes``).
FRONTIER_PAYLOAD = "bits"


def frontier_table_bytes(E, width: int) -> int:
    """Bytes of the table one tile's degree class gathers from in a
    width-``width`` level (``ellmat._ell_local_frontier``): a word a
    local column and the pad slots' empty one, for every 32 lanes."""
    from ..parallel.ellmat import WORD_LANES

    return 4 * (E.local_cols + 1) * -(-int(width) // WORD_LANES)


def _bfs_batch_tallied(A, sources, max_iters, track_levels, csc=None):
    """``_bfs_batch_impl`` plus, as a fourth output, the ``int32[pr, pc,
    classes, 2]`` tally over the whole search of each tile's and degree
    class's sweeps run dense / skipped (``ellmat.SWEEP_MODES``; left per
    tile: a level waits for its busiest one, and the host weighs the
    counts by ``ellmat.class_slots``) and, as a fifth, what its pushes
    did (a ``PushReport``; None for a program with no push in it).  Not
    jitted: the served plan (``engine._build_plan``) traces it into its
    own program.

    The loop carries the frontier as MEMBERSHIP, not as ids: ``member
    [pc, lc, ceil(W / 32)]`` int32, col-aligned, bit ``l`` of word ``w``
    set where the column is in the frontier of lane ``32 w + l``.  The id
    a sweep's slot would fetch from a table of ids is its own column's,
    so a level gathers the word and makes the candidate itself
    (``ellmat.ell_frontier_sweep``): a sixteenth of the 16-lane table to
    gather from and to realign, and one table at every width to 32.

    A pull sweep costs what the matrix holds, whatever the frontier: it
    finds the frontier's neighbours by gathering every slot of every
    degree class that still has an unvisited row.  Given ``csc``
    (``(indptr, rowidx, current)``: ``ellmat.build_csc_companion`` of
    ``A``'s edges and a bool scalar, False once they have moved on) a
    level is taken instead as a walk of its frontiers' own columns
    (``ellmat.ell_frontier_push``, scope ``bfs.push`` inside the loop)
    whenever the device finds, from the level's frontier, that the
    companion is current and those columns hold at most
    ``push_capacity(A)`` edges on every tile (``PUSH_EDGE_CAPACITY``;
    ``ellmat.ell_frontier_fit``: one pass over the membership words).
    That is level 0 of any search (W columns), the thin last levels of a
    Graph500 search, and every level of a search over a bounded-degree,
    high-diameter graph, whose frontier never holds more than a few
    thousand vertices a lane.  Any other level is the class sweep.  One
    ``cond`` a level, both branches give the level's candidates; no
    gather table crosses a branch (``ellmat._ell_class_sweeps``).
    Parents, levels, ``niter`` and every tie are the all-pull program's,
    bit for bit (the push folds with the same max over in-frontier
    neighbours' ids); a caller without a companion gets the all-pull
    program."""
    from ..parallel.vec import DistMultiVec
    from ..parallel.ellmat import (
        SWEEP_MODES, ell_frontier_fit, ell_frontier_push,
        ell_frontier_sweep, pack_lanes, tile_lines,
    )

    grid = A.grid
    n = A.nrows
    pr_, lr = grid.pr, grid.local_rows(n)
    pc_, lc = grid.pc, grid.local_cols(A.ncols)
    iters = max_iters if max_iters is not None else n
    pushing = csc is not None and iters > 0

    with jax.named_scope("bfs.init"):
        row_gids = _global_ids(grid, pr_, lr, n, "row")  # [pr, lr]
        col_gids = _global_ids(grid, pc_, lc, A.ncols, "col")

        src = sources.astype(jnp.int32)[None, None, :]  # [1, 1, W]
        W = src.shape[-1]
        # PAD_ROOT lanes (the serve batcher's lane padding) are inert:
        # the live guard keeps a pad source from matching the -1 padding
        # slots of the gid tables, so a pad lane starts (and stays) empty.
        live = src != PAD_ROOT
        is_src = (row_gids[:, :, None] == src) & live
        parents0 = jnp.where(is_src, src, jnp.int32(-1))  # [pr, lr, W]
        levels0 = (
            jnp.where(is_src, 0, -1).astype(jnp.int32)
            if track_levels
            else jnp.zeros((1, 1, 1), jnp.int32)  # placeholder carry
        )
        member0 = pack_lanes((col_gids[:, :, None] == src) & live)
        if pushing:
            indptr, rowidx, current = csc
            current = jnp.asarray(current, jnp.bool_)
            # what the levels walk, laid out for them ONCE, here
            coldeg, indptr, rowidx = tile_lines(
                grid, indptr[..., 1:] - indptr[..., :-1], indptr, rowidx)
            capacity = push_capacity(A)

    def cond(state):
        _, _, _, level, active, _, _ = state
        return active & (level < iters)

    def advance(parents, levels, level, y):
        """What a level does with its candidates ``y`` [pr, lr, W]."""
        with jax.named_scope("bfs.update"):
            new = (
                (y >= 0) & (parents < 0)
                & (row_gids[:, :, None] >= 0)
            )
            parents = jnp.where(new, y, parents)
            if track_levels:
                levels = jnp.where(new, level + 1, levels)
            found = pack_lanes(new)  # [pr, lr, nw]
        member = DistMultiVec(
            blocks=found, length=n, align="row", grid=grid
        ).realign("col").blocks
        with jax.named_scope("bfs.active"):
            active = jnp.any(new)
        return parents, levels, member, level + 1, active

    def pull(parents, member):
        return ell_frontier_sweep(A, member, parents < 0)

    def step(state):
        parents, levels, member, level, _, tally, report = state
        if not pushing:
            y, sweeps = pull(parents, member)
        else:
            with jax.named_scope("bfs.push"):
                fits, edges = ell_frontier_fit(A, coldeg, member, capacity)
                take = current & fits

            def push(_parents, member):
                with jax.named_scope("bfs.push"):
                    y, passes = ell_frontier_push(
                        A, indptr, rowidx, member, W, capacity)
                return y, jnp.zeros_like(tally), passes

            y, sweeps, passes = jax.lax.cond(
                take, push, lambda *level: (*pull(*level), jnp.zeros_like(
                    report.passes)), parents, member)
            report = PushReport(
                outcome=jnp.where(
                    level > 0, report.outcome,
                    jnp.where(current, jnp.where(fits, 0, 1), 2),
                ).astype(jnp.int32),
                levels=report.levels + take.astype(jnp.int32),
                edges=report.edges + jnp.where(take, edges, 0),
                passes=report.passes + passes,
            )
        return (*advance(parents, levels, level, y), tally + sweeps, report)

    state = (
        parents0, levels0, member0, jnp.int32(0), jnp.bool_(True),
        jnp.zeros((pr_, pc_, len(A.buckets), len(SWEEP_MODES)), jnp.int32),
        PushReport(
            outcome=jnp.int32(PUSH_OUTCOMES.index("stale")),
            levels=jnp.int32(0), edges=jnp.zeros((pr_, pc_), jnp.int32),
            passes=jnp.zeros((pr_, pc_), jnp.int32),
        ) if pushing else None,
    )
    # the whole loop, condition included, is one scope: a level is one
    # iteration of it in the device trace
    with jax.named_scope("bfs.level"):
        parents, levels, _, niter, _, tally, report = jax.lax.while_loop(
            cond, step, state
        )
    if not track_levels:
        # levels were not tracked: return discovery indicator (0 for the
        # sources / discovered? -1 undiscovered) — parents' sign carries it.
        levels = jnp.where(parents >= 0, 0, -1)
    return parents, levels, niter, tally, report


@lru_cache(maxsize=16)
def _gid_blocks(grid, nblocks: int, block_len: int, length: int,
                align: str):
    """Materialized global-id blocks (``_global_ids`` as a DEVICE BUFFER,
    built host-side and uploaded once per (grid, shape)).

    BOUNDED cache: each entry pins an HBM buffer for its
    (grid, shape, align); unbounded, a long-lived process sweeping many
    shapes (the pytest session) would accumulate pinned device memory
    forever. 16 entries cover any realistic working set; eviction
    just re-uploads. Growth is
    visible through the ``cache.bfs.*`` gauges (``obs`` registry) and
    ``clear_bfs_caches()`` is the explicit release hook.

    Why not jnp.arange inside the jitted program: on the target backend
    an iota-derived gid table fuses into the while-loop body as a
    per-iteration rematerialization that executes SERIALLY — the
    otherwise-identical single-root BFS program measured 39.5 s with the
    in-program iota vs 1.7 s with the table passed as an operand
    (round-5 sequence probe, modes v9 vs v7; ROADMAP D14)."""
    import numpy as np

    g = np.arange(nblocks * block_len, dtype=np.int32).reshape(
        nblocks, block_len
    )
    g = np.where(g < length, g, -1)
    if grid.size == 1:
        # UNSHARDED on purpose: a NamedSharding'd vector operand makes
        # the whole compiled program execute ~25x slower on the target
        # backend (round-5 probe: 47.3 s vs 1.7 s — same loop, only
        # the gid operands' sharding differs)
        return jax.device_put(jnp.asarray(g))
    sh = (
        grid.row_aligned_sharding() if align == "row"
        else grid.col_aligned_sharding()
    )
    return jax.device_put(jnp.asarray(g), sh)


#: Global degree-class ladder shared by every bfs_single tier: class c
#: holds vertices with degree in (LADDER[c-1], LADDER[c]]; degrees past
#: the last rung only ever run the dense sweep.
BFS_CLASS_LADDER = (8, 64, 512, 4096, 32768, 131072)


@lru_cache(maxsize=8)
def _iota_operand(kmax: int):
    """[kmax] iota as a materialized device buffer — in-program iotas
    serialize inside while-loop fusions on the target backend (the v9
    pathology, see _gid_blocks). Bounded like ``_gid_blocks``."""
    import numpy as np

    return jax.device_put(jnp.asarray(np.arange(kmax, dtype=np.int32)))


def clear_bfs_caches() -> None:
    """Explicit release hook for every BFS-side cache: the gid/iota
    DEVICE BUFFERS and the jitted single-root programs that close over
    them (``_bfs_single_program``). Frees the pinned HBM; the next call
    rebuilds (ADVICE r5)."""
    _gid_blocks.cache_clear()
    _iota_operand.cache_clear()
    _bfs_single_program.cache_clear()


def _record_bfs_cache_stats() -> None:
    """obs provider: lru_cache hit/miss/size gauges, polled at export
    time so cache growth is visible without a counter on every access."""
    for label, fn in (
        ("gid_blocks", _gid_blocks),
        ("iota_operand", _iota_operand),
        ("single_program", _bfs_single_program),
    ):
        ci = fn.cache_info()
        obs.gauge(f"cache.bfs.{label}.hits", ci.hits)
        obs.gauge(f"cache.bfs.{label}.misses", ci.misses)
        obs.gauge(f"cache.bfs.{label}.size", ci.currsize)
        obs.gauge(f"cache.bfs.{label}.maxsize", ci.maxsize)


obs.register_provider(_record_bfs_cache_stats)


def bfs_single(E, source, csc, *, tiers, csr=None, coldeg=None,
               rowdeg=None, max_iters: int | None = None):
    """Frontier/undiscovered-proportional single-root BFS — see
    ``_bfs_single_program`` for the design. This wrapper resolves the
    cached program for (grid, shape, tiers) and fills test-path
    fallbacks: ``csr`` (per-tile row-major companion,
    ``ellmat.build_csr_companion`` — required for "bu" tiers),
    ``coldeg``/``rowdeg`` (global degree vectors as [pc, lc] / [pr, lr]
    blocks; pass precomputed blocks on the real chip).

    Returns (parents DistVec i32, levels DistVec i32, num_iters).
    """
    from ..semiring import PLUS_TIMES
    from ..parallel.spmat import ones_i32

    grid = E.grid
    if any(kind == "bu" for kind, _ in tiers) and csr is None:
        raise ValueError(
            "bu tiers need the row-major companion: "
            "csr=build_csr_companion(grid, rows, cols, nrows, ncols)"
        )
    if rowdeg is None:
        rowdeg = E.reduce(PLUS_TIMES, "cols", map_fn=ones_i32).blocks
    if coldeg is None:
        # test fallback; chip callers pass host-built blocks (the CSC
        # indptr derivation is the probe-v6 megascale-1-D pathology)
        rd = DistVec(
            blocks=rowdeg, length=E.nrows, align="row", grid=grid
        )
        coldeg = rd.realign("col").blocks
    if csr is None:
        csr = csc  # placeholder operand; no "bu" tier traces it
    run = _bfs_single_program(
        grid, E.nrows, E.ncols, len(E.buckets), tiers, max_iters
    )
    flat = [a for b in E.buckets for a in b]
    parents, levels, niter = run(
        jnp.int32(source), csc[0], csc[1], csr[0], csr[1], coldeg,
        rowdeg, *flat,
    )
    mk = lambda b: DistVec(blocks=b, length=E.nrows, align="row",
                           grid=grid)
    return mk(parents), mk(levels), niter


#: Default sequential-root tier ladder for Graph500-class graphs at
#: scale ~20 (sized from the measured level anatomy in
#: round 5): a small top-down tier for the pre-peak
#: levels, two bottom-up tiers for the post-peak levels, dense for the
#: peak step.
DEFAULT_SEQ_TIERS = (
    "td:1024,1024,512,128,16,2"
    "|bu:524288,16384,1024,0,0,0"
    "|bu:1048576,32768,2048,128,0,0"
)


def parse_tier_spec(spec: str):
    """``"td:1024,1024,512,128,16,2|bu:524288,16384,1024,0,0,0"`` →
    bfs_single tier tuple. Empty string → () (always-dense)."""
    tiers = []
    for part in spec.split("|"):
        if not part:
            continue
        kind, _, budg = part.partition(":")
        budgets = tuple(int(v) for v in budg.split(","))
        if kind not in ("td", "bu") or len(budgets) != len(
            BFS_CLASS_LADDER
        ):
            raise ValueError(
                f"bad tier spec {part!r}: want kind td|bu and "
                f"{len(BFS_CLASS_LADDER)} budgets"
            )
        tiers.append((kind, budgets))
    return tuple(tiers)


@lru_cache(maxsize=32)
def _bfs_single_program(grid, nrows, ncols, nbuckets, tiers,
                        max_iters: int | None = None):
    """Single-root BFS whose per-level cost follows the DIRECTION-OPTIMIZED
    work profile, not nnz — the Graph500 spec's SEQUENTIAL kernel 2
    (``TopDownBFS.cpp:437-479``; work ∝ frontier is the reference's
    top-down property, ``BFSFriends.h:59-182``; the bottom-up regime is
    Beamer's, ``DirOptBFS.cpp:374-424``).

    Measured scale-20 R-MAT level anatomy (round 5, host
    profile): one step is heavy (expanding L2: 6-26M frontier edges —
    the dense sweep's regime), the steps before it have TINY frontiers
    (≤350K edges), and from L3 on the UNDISCOVERED side collapses
    (31K-445K edges among undiscovered rows). So each level picks, on
    device, the first fitting strategy from ``tiers``:

      ("td", budgets) — top-down class-bucketed CSC column walk: active
        columns are degree-classed on ``BFS_CLASS_LADDER``, compacted by
        ONE top_k (sort), and each class c walks at most budgets[c]
        columns with a [F_c, K_c] static gather; parents scatter-max
        into rows. Work ∝ Σ F_c·K_c (~1.5M slots for the default small
        tier).
      ("bu", budgets) — bottom-up class-bucketed CSR row walk: same
        machinery over UNDISCOVERED rows; each row folds its in-edge
        candidates with a gather (NO edge-sized scatter — the r1 lesson
        that built EllParMat), then one [ΣF_c]-sized row scatter.
      else — the dense ELL gather sweep (cost ~nnz slots, 0.3 s at
        scale 20).

    Budget semantics: class c may hold at most budgets[c] active
    vertices (0 = none allowed); any vertex past the ladder's last rung
    forces the next strategy. Conditions are 7 masked reductions per
    side per level, computed once.

    TPU-pathology notes baked into this design (round-5 sequence probe,
    a machine that is gone; not re-measured, ROADMAP D14):
    in-program iota/cumsum/1-D megascatter serialize on this backend
    (1.6-1.9 s per 1M elements; 39.5 s-vs-1.7 s for the v9/v7 program
    pair), so compaction is top_k (sort, ~50 ms/M), iota and gid tables
    are passed as materialized operands, and all index math is gathers.

    W=1 also kills the batch kernels' two other single-root taxes: the
    gather payload is a SCALAR (no 128-lane padding waste), and parents
    ride the gathers directly as int32 candidates (no reconstruction
    pass) — the frontier value of column c is c's global id, exactly
    the reference's SelectMax parent semantics (Semirings.h:166).

    Whole traversal is ONE launch (lax.while_loop + lax.switch; zero
    host readbacks).

    CLOSURE-CONSTANT LAW (this backend, measured): the gid/iota tables
    must be CLOSED OVER by the jitted program, not passed as arguments —
    the identical loop runs 1.65 s with them as closure constants and
    27.3 s as parameters (round-5 probe w4 vs w7; in-program jnp.arange
    is 39.5 s, v9). Hence this factory: one cached jitted program per
    (grid, shape, tiers), taking only the per-graph arrays as arguments.

    Returns ``run(source, csc_indptr, csc_rowidx, csr_indptr,
    csr_colidx, coldeg, rowdeg, *flat_bucket_arrays) -> (parents,
    levels, niter)`` over plain [pr, lr] block arrays.
    """
    import functools

    from jax.sharding import PartitionSpec as P

    from ..parallel.grid import COL_AXIS, ROW_AXIS
    from ..parallel.spmat import TILE_SPEC

    n = nrows
    lr = grid.local_rows(n)
    lc = grid.local_cols(ncols)
    nb = nbuckets
    iters = max_iters if max_iters is not None else n
    LADDER = BFS_CLASS_LADDER
    NC = len(LADDER)
    assert lc <= 1 << 21 and lr <= 1 << 21, "class sort packs ids in 21 bits"
    row_gids = _gid_blocks(grid, grid.pr, lr, n, "row")
    col_gids = _gid_blocks(grid, grid.pc, lc, ncols, "col")
    iota_k = _iota_operand(LADDER[-1])

    @jax.jit
    def run(source, csc_indptr, csc_rowidx, csr_indptr, csr_colidx,
            coldeg, rowdeg, *flat_args):
        parents0 = jnp.where(row_gids == source, jnp.int32(source), -1)
        levels0 = jnp.where(row_gids == source, 0, -1).astype(jnp.int32)
        # frontier: col-aligned int32 parent candidates (vertex's own
        # global id when in the frontier, -1 inactive)
        x0 = jnp.where(col_gids == source, jnp.int32(source), -1)

        def classify(d):
            """Degree → ladder class (0..NC-1; NC = beyond the ladder)."""
            c = jnp.zeros_like(d)
            for K in LADDER:
                c = c + (d > K).astype(d.dtype)
            return c

        def class_counts(mask, degblocks):
            """[NC+1] active-vertex count per class (last = beyond ladder)."""
            d = jnp.where(mask, degblocks, -1)
            lo = -1
            cnts = []
            for K in LADDER:
                cnts.append(jnp.sum(((d > lo) & (d <= K)).astype(jnp.int32)))
                lo = K
            cnts.append(jnp.sum((d > LADDER[-1]).astype(jnp.int32)))
            return cnts

        def dense_level(x, undisc):
            """Dense ELL gather sweep (the heavy-step regime): one
            scalar-payload gather over every ELL slot, parents carried."""

            def body(xblk, ublk, *flat):
                buckets = [
                    tuple(a[0, 0] for a in flat[3 * i : 3 * i + 3])
                    for i in range(nb)
                ]
                xv = xblk[0]  # [lc] i32 candidates
                xpad = jnp.concatenate([xv, jnp.full((1,), -1, jnp.int32)])
                y = jnp.full((lr,), -1, jnp.int32)
                for bc, _bv, br in buckets:
                    g = xpad[jnp.minimum(bc, lc)]  # [nb_, kb] i32
                    yb = jnp.max(g, axis=1)
                    y = y.at[br].max(yb, mode="drop")
                y = jnp.where(ublk[0], y, -1)
                return jax.lax.pmax(y, COL_AXIS)[None]

            return jax.shard_map(
                body, mesh=grid.mesh,
                in_specs=(P(COL_AXIS), P(ROW_AXIS)) + (TILE_SPEC,) * (3 * nb),
                out_specs=P(ROW_AXIS),
                check_vma=False,
            )(x, undisc, *flat_args)

        def _classed_walk(kind, budgets):
            """Shared class-bucketed walk for both directions.

            td: compact ACTIVE COLUMNS, walk their CSC ranges, scatter-max
            parent candidates into rows ([F_c, K_c] edge scatter).
            bu: compact UNDISCOVERED ROWS, walk their CSR ranges, fold each
            row's neighbor candidates by gather-max, one [F_c] row scatter.
            """
            # cap per-class budgets at the block length: oversized
            # static budgets (tuned for scale 20) would make small-graph
            # walks gather more slots than the whole matrix
            L_cap = lc if kind == "td" else lr
            budgets = tuple(min(b, L_cap) for b in budgets)
            FT = sum(b for b in budgets if b > 0)

            def body(ipt, vidx, iota, xblk, ublk, cdgb, rdgb, gidb):
                indptr = ipt[0, 0]
                vid = vidx[0, 0]  # csc: rowidx / csr: colidx
                xv = xblk[0]  # [lc] i32 frontier candidates
                ub = ublk[0]  # [lr] bool undiscovered
                xpad = jnp.concatenate([xv, jnp.full((1,), -1, jnp.int32)])
                ipt_pad = jnp.concatenate([indptr, indptr[-1:]])
                if kind == "td":
                    L, gdeg, gid = lc, cdgb[0], gidb[0]
                    active = xv >= 0
                    ax = COL_AXIS
                else:
                    L, gdeg, gid = lr, rdgb[0], gidb[0]
                    active = ub & (gid >= 0)
                    ax = ROW_AXIS
                j = jax.lax.axis_index(ax)
                lid = gid - j * L  # local index within this block
                dcls = classify(gdeg)
                key = jnp.where(
                    active & (dcls < NC),
                    ((NC - dcls) << 21) | lid,
                    -1,
                )
                k = min(FT, L)
                topv, _ = jax.lax.top_k(key, k)  # class-asc, id-desc blocks
                ids = jnp.where(topv >= 0, topv & 0x1FFFFF, L)
                if k < FT:
                    ids = jnp.pad(ids, (0, FT - k), constant_values=L)
                # per-class starts (tiny scalar chain, not a prefix op)
                d_act = jnp.where(active, gdeg, -1)
                lo = -1
                starts, start = [], jnp.int32(0)
                for K in LADDER:
                    starts.append(start)
                    start = start + jnp.sum(
                        ((d_act > lo) & (d_act <= K)).astype(jnp.int32)
                    )
                    lo = K
                cap = vid.shape[0]
                gdeg_pad = jnp.concatenate([gdeg, jnp.zeros((1,), gdeg.dtype)])
                y = jnp.full((lr,), -1, jnp.int32)
                lo = -1
                for c, K in enumerate(LADDER):
                    F = budgets[c]
                    if F <= 0:
                        lo = K
                        continue
                    sl = jax.lax.dynamic_slice(ids, (starts[c],), (F,))
                    safe = jnp.minimum(sl, L)
                    gd = gdeg_pad[safe]
                    # class membership re-check excludes clamp/pad strays
                    okc = (sl < L) & (gd > lo) & (gd <= K)
                    st = ipt_pad[safe]
                    ldeg = ipt_pad[jnp.minimum(sl + 1, L)] - st
                    ik = iota[:K][None, :]  # static slice of the operand
                    valid = okc[:, None] & (ik < ldeg[:, None])
                    slot = jnp.where(valid, st[:, None] + ik, cap - 1)
                    other = jnp.where(valid, vid[slot], lc)
                    if kind == "td":
                        # scatter parent candidates into target rows
                        tgt = jnp.where(valid, other, lr)
                        contrib = jnp.where(
                            valid, xpad[jnp.minimum(safe, lc)][:, None], -1
                        )
                        y = y.at[tgt].max(contrib, mode="drop")
                    else:
                        # fold neighbor candidates per row, tiny row scatter
                        g = jnp.where(
                            valid, xpad[jnp.minimum(other, lc)], -1
                        )
                        yb = jnp.max(g, axis=1)  # [F]
                        y = y.at[jnp.where(okc, sl, lr)].max(
                            yb, mode="drop"
                        )
                    lo = K
                y = jnp.where(ub, y, -1)
                return jax.lax.pmax(y, COL_AXIS)[None]

            ipt, vidx = (csc_indptr, csc_rowidx) if kind == "td" else (
                csr_indptr, csr_colidx
            )
            gidb = col_gids if kind == "td" else row_gids
            gid_spec = P(COL_AXIS) if kind == "td" else P(ROW_AXIS)

            def run(x, undisc):
                return jax.shard_map(
                    body, mesh=grid.mesh,
                    in_specs=(TILE_SPEC, TILE_SPEC, P(), P(COL_AXIS),
                              P(ROW_AXIS), P(COL_AXIS), P(ROW_AXIS),
                              gid_spec),
                    out_specs=P(ROW_AXIS),
                    check_vma=False,
                )(ipt, vidx, iota_k, x, undisc, coldeg, rowdeg, gidb)

            return run

        branches = [
            _classed_walk(kind, budgets) for kind, budgets in tiers
        ] + [dense_level]

        def cond(state):
            _, _, _, level, active = state
            return active & (level < iters)

        def step(state):
            parents, levels, x, level, _ = state
            undisc = parents < 0
            if tiers:
                fc = class_counts(x >= 0, coldeg)
                uc = class_counts(undisc & (row_gids >= 0), rowdeg)
                sel = jnp.int32(len(tiers))
                for t in reversed(range(len(tiers))):
                    kind, budgets = tiers[t]
                    cnts = fc if kind == "td" else uc
                    ok = cnts[NC] == 0
                    for c in range(NC):
                        ok = ok & (cnts[c] <= budgets[c])
                    sel = jnp.where(ok, jnp.int32(t), sel)
                y = jax.lax.switch(sel, branches, x, undisc)
            else:
                y = dense_level(x, undisc)  # tiers=(): always-dense path
            new = (y >= 0) & undisc & (row_gids >= 0)
            parents = jnp.where(new, y, parents)
            levels = jnp.where(new, level + 1, levels)
            frontier_row = DistVec(
                blocks=jnp.where(new, row_gids, -1), length=n, align="row",
                grid=grid,
            )
            x_next = frontier_row.realign("col").blocks
            return parents, levels, x_next, level + 1, jnp.any(new)

        parents, levels, _, niter, _ = jax.lax.while_loop(
            cond, step, (parents0, levels0, x0, jnp.int32(0),
                         jnp.bool_(True))
        )
        # PLAIN ARRAYS out: DistVec-wrapping inside the jit executes
        # ~60x slower on this backend (probe wa 1.6 s vs wc 110 s)
        return parents, levels, niter

    return run


@jax.jit
def single_traversed_edges(deg_row_blocks, parents: DistVec) -> jax.Array:
    """Kernel-2 edge count for one root, on device (uint32-safe like
    ``batch_traversed_edges``): sum of degrees over discovered / 2."""
    disc = parents.blocks >= 0  # [pr, lr]
    te = jnp.sum(
        jnp.where(disc, deg_row_blocks, 0).astype(jnp.uint32)
    )
    return (te // 2).astype(jnp.int32)


@jax.jit
def batch_traversed_edges(deg_row_blocks, parents) -> jax.Array:
    """Graph500 kernel-2 edge count per root, ON DEVICE: [W] int array of
    (sum of degrees over discovered vertices) / 2 — so the benchmark's only
    D2H readback is one tiny vector AFTER the timed batch.

    ``deg_row_blocks``: [pr, lr] structural out-degrees (row-aligned,
    padding 0); ``parents``: the DistMultiVec from ``bfs_batch``.
    """
    disc = parents.blocks >= 0  # [pr, lr, W]
    # uint32 accumulation: a giant component's per-root degree sum can reach
    # the full symmetrized endpoint count ~2^(scale+5) at edgefactor 16,
    # which crosses 2^31 near scale 26 — uint32 extends the safe range to
    # scale ~27 (the [W] output is tiny, so width costs nothing).
    te = jnp.sum(
        jnp.where(disc, deg_row_blocks[:, :, None], 0).astype(jnp.uint32),
        axis=(0, 1),
    )
    return (te // 2).astype(jnp.int32)


def bfs_batch_compact(A, sources, max_iters: int | None = None,
                      ring: bool = False, csc=None,
                      frontier_capacity: int | None = None,
                      edge_capacity: int | None = None):
    """Eager wrapper: the jitted program returns plain block arrays (the
    plain-outputs law — DistVec/DistMultiVec dataclass wrapping inside
    jit measured 60x slower on the round-5 machine);
    this wrapper rebuilds the DistMultiVecs outside.

    For graphs no deeper than 126 levels from any root (its levels are
    int8): a Graph500 R-MAT is 6-8.  A road network, a mesh or a random
    geometric graph is hundreds to thousands; there a search stops at
    level 126 with the rest unreached.  A deep graph goes through the
    served path (``GraphEngine.from_coo(kinds=("bfs",))`` ->
    ``Server.submit("bfs", root)``: int32 levels, and every thin level
    a walk of its frontier's columns, ``_bfs_batch_tallied``) or, as a
    library call without a companion, through ``bfs_batch`` (int32
    levels, a whole sweep a level)."""
    from ..parallel.vec import DistMultiVec

    opts = dict(
        max_iters=max_iters, ring=ring, csc=csc,
        frontier_capacity=frontier_capacity, edge_capacity=edge_capacity,
    )
    p, l, niter = _bfs_batch_compact_program(A, sources, **opts)
    if obs.ENABLED:
        # this entry has no warm-up of its own: the first traced call of
        # a shape publishes the program's op names (obs/opnames.py),
        # AFTER the call, so the call pays for the program as an
        # untraced one does and the publishing only for itself
        obs.opnames.publish_once(
            ("bfs_batch_compact", A.grid, A.nrows, A.ncols,
             len(A.buckets), sources.shape, max_iters, ring,
             csc is not None, frontier_capacity, edge_capacity),
            lambda: _bfs_batch_compact_program.lower(
                A, sources, **opts
            ).compile().as_text(),
        )
    mk = lambda b: DistMultiVec(
        blocks=b, length=A.nrows, align="row", grid=A.grid
    )
    return mk(p), mk(l), niter


@partial(
    jax.jit,
    static_argnames=("max_iters", "ring", "frontier_capacity",
                     "edge_capacity"),
)
def _bfs_batch_compact_program(A, sources, max_iters: int | None = None,
                               ring: bool = False, csc=None,
                               frontier_capacity: int | None = None,
                               edge_capacity: int | None = None):
    """Level-compressed multi-source BFS: int8 frontiers, parents
    reconstructed in ONE pass after the search.

    ``bfs_batch`` carries int32 parent candidates through every gather —
    4W bytes of payload per gathered index. This variant carries only a
    one-byte level indicator per root (W bytes/index): the search loop
    discovers LEVELS, and parents come from a single final sweep picking,
    per (vertex, root), the max-id in-neighbor at level-1 (any valid
    Graph500 tree; the reference's SelectMax tie-break). On
    payload-width-sensitive gather hardware this cuts dense-level cost
    ~3-4x at W=256 and halves the memory footprint (int8 state).

    Level range: int8 caps at 126 levels — far beyond any Graph500 R-MAT
    diameter; ``max_iters`` defaults to that cap, and a search of a
    deeper graph (a road network, a random geometric graph: hundreds of
    levels) ends there with the rest unreached: see ``bfs_batch_compact``
    for what to call instead.

    ``ring=True`` folds each level's partials with the explicit
    ppermute carousel schedule (``collectives.axis_ring_reduce`` — the
    BitMapCarousel analog, neighbor-only ICI traffic) instead of the
    fused all-reduce; results are identical.

    Direction optimization for the batch: pass ``csc`` (the
    ``ellmat.build_csc_companion`` arrays) plus static ``frontier_capacity``
    / ``edge_capacity`` budgets, and each level checks ON DEVICE whether
    the UNION of all W frontiers fits the budgets — if so it walks only
    those columns' edges (cost ∝ budgets) instead of the full dense sweep
    (cost ∝ nnz). First levels and the straggler tail of a 256-root batch
    are exactly this regime. ``lax.cond`` keeps both kernels compiled
    once; zero host readbacks.

    Returns (parents int32 blocks, levels int8 blocks, num_iters) —
    PLAIN ARRAYS (the eager wrapper above rebuilds the DistMultiVecs) —
    with the same conventions as ``bfs_batch``.
    """
    from ..parallel.ellmat import (
        EllParMat,
        _ell_levels_step,
        _ell_parents_from_levels,
        _ell_union_sparse_step,
    )
    from ..parallel.vec import DistMultiVec
    from ..parallel.grid import COL_AXIS, ROW_AXIS
    from jax.sharding import PartitionSpec as P

    grid = A.grid
    n = A.nrows
    pr_, lr = grid.pr, grid.local_rows(n)
    pc_, lc = grid.pc, grid.local_cols(A.ncols)
    W = sources.shape[0]
    if max_iters is not None and max_iters > 126:
        raise ValueError(
            f"bfs_batch_compact stores levels as int8 (max depth 126); "
            f"max_iters={max_iters} cannot be honored — a graph deeper "
            "than 126 levels goes through the served path "
            "(Server.submit('bfs', root): int32 levels, thin levels walked "
            "from the frontier) or bfs_batch (int32 levels, a sweep a level)"
        )
    iters = max_iters if max_iters is not None else 126

    with jax.named_scope("bfs.init"):
        row_gids = _global_ids(grid, pr_, lr, n, "row")
        col_gids = _global_ids(grid, pc_, lc, A.ncols, "col")
        src = sources.astype(jnp.int32)[None, None, :]
        # PAD_ROOT lanes stay empty (see _bfs_batch_impl's live guard)
        live = src != PAD_ROOT

        levels0 = jnp.where(
            (row_gids[:, :, None] == src) & live, 0, -1
        ).astype(jnp.int8)  # [pr, lr, W]
        x0 = ((col_gids[:, :, None] == src) & live).astype(
            jnp.int8
        )  # [pc, lc, W]

    def mk(b, align):
        return DistMultiVec(blocks=b, length=n, align=align, grid=grid)

    diropt = (
        csc is not None
        and frontier_capacity is not None
        and edge_capacity is not None
    )
    if diropt:
        csc_indptr, csc_rowidx = csc

        def colde_body(ipt):
            d = ipt[0, 0][1:] - ipt[0, 0][:-1]
            return jax.lax.psum(d, ROW_AXIS)[None]

        coldeg = jax.shard_map(
            colde_body,
            mesh=grid.mesh,
            in_specs=(P(ROW_AXIS, COL_AXIS),),
            out_specs=P(COL_AXIS),
            check_vma=False,
        )(csc_indptr)  # [pc, lc] per-column degrees

    def cond(state):
        _, _, level, active = state
        return active & (level < iters)

    def step(state):
        levels, x, level, _ = state
        undisc = (levels < 0).astype(jnp.int8)
        if diropt:
            act = jnp.max(x, axis=2) > 0  # [pc, lc] union frontier
            cnt = jnp.sum(act.astype(jnp.int32))
            edges = jnp.sum(jnp.where(act, coldeg, 0))
            use_sparse = (cnt <= frontier_capacity) & (
                edges <= edge_capacity
            )
            reached = jax.lax.cond(
                use_sparse,
                lambda a: _ell_union_sparse_step(
                    A, csc_indptr, csc_rowidx, a[0], a[1],
                    frontier_capacity, edge_capacity,
                ),
                lambda a: _ell_levels_step(A, a[0], a[1], ring=ring),
                (x, undisc),
            )
        else:
            reached = _ell_levels_step(A, x, undisc, ring=ring)
        with jax.named_scope("bfs.update"):
            new = reached > 0
            levels = jnp.where(
                new, (level + 1).astype(jnp.int8), levels
            )
        x_next = mk(reached, "row").realign("col").blocks
        with jax.named_scope("bfs.active"):
            active = jnp.any(new)
        return levels, x_next, level + 1, active

    with jax.named_scope("bfs.level"):
        levels, _, niter, _ = jax.lax.while_loop(
            cond, step, (levels0, x0, jnp.int8(0), jnp.bool_(True))
        )

    with jax.named_scope("bfs.parents"):
        levels_col = mk(levels, "row").realign("col").blocks
        parents = _ell_parents_from_levels(A, levels_col, levels)
        # roots are their own parents; undiscovered stay -1
        parents = jnp.where(
            (row_gids[:, :, None] == src) & live, src, parents
        )
        parents = jnp.where(
            (levels < 0) | (row_gids[:, :, None] < 0), -1, parents
        )
    # plain arrays out (see the eager wrapper above)
    return parents, levels, niter.astype(jnp.int32)
