"""Bipartite matchings (≈ Applications/BipartiteMatchings/).

The reference ships three layers (``BPMaximalMatching.h``,
``BPMaximumMatching.cpp:124-188``, ``ApproxWeightPerfectMatching.h``):

1. **Maximal matching** — greedy and Karp-Sipser initializations, expressed
   as rounds of (rows propose a free column; columns grant to one proposer).
   Here a round is: per-row masked structural min over free columns (a
   Reduce(Row) on a column-id matrix), a ``scatter_combine`` granting each
   column to its minimum proposer, and a scatter back to the rows — all
   distributed, no host data movement inside a round.
2. **Maximum cardinality matching** — augmenting-path phases, each
   entirely on the device (``_alternating_phase``): alternating layers
   grown from the free rows, the free columns found chased back along
   their parents, a vertex-disjoint set of the paths chosen by winner
   selection and augmented in parallel (the analog of the reference's
   serial augment over its locally-owned queue,
   BPMaximumMatching.cpp:156-188).  ``maximum_matching(device=False)``
   keeps the first prototype, which augmented on the host over gathered
   pointer arrays, as the validation oracle.
3. **AWPM** — heaviest-edge Karp-Sipser initialization + cardinality
   augmentation, the composition of the reference's AWPM driver.

``mcm_job`` is the whole of 1 and 2 as one library call (upstream's
``bpmm`` driver): over an ``SpParMat`` the loops above, a whole pass of
the matrix a round and a layer and a scalar read back a turn; over a
``BipartiteEll`` (the pattern as ELL buckets and column lists, both
ways) one program whose rounds and layers cost what is live in them.
The operand's type picks, as ``models/cc.py:fastsv``'s does.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import obs
from ..semiring import MAX_MIN, PLUS_TIMES, SELECT2ND_MIN
from ..parallel.ellmat import (
    SWEEP_MODES, EllParMat, class_slots, count_sweep_work, dist_spmv_ell,
    ell_frontier_fit, ell_frontier_push, ell_frontier_sweep, pack_lanes,
    tile_lines,
)
from ..parallel.grid import COL_AXIS, ROW_AXIS
from ..parallel.spmat import SpParMat, TILE_SPEC, ones_f32
from ..parallel.spmv import dist_spmv
from ..parallel.vec import DistMultiVec, DistVec
from .bfs import push_capacity

I32MAX = np.int32(np.iinfo(np.int32).max)


def _set_colid_vals(t, ro, co):
    vals = jnp.where(t.valid_mask(), (t.cols + co).astype(jnp.int32), I32MAX)
    return dataclasses.replace(t, vals=vals)


def _colid_matrix(A: SpParMat) -> SpParMat:
    """A with values replaced by global column ids (int32)."""
    return A.tile_map_indexed(_set_colid_vals)


def _mask_free_ids(v, free):
    return jnp.where(free, v, I32MAX)


def _mask_free_weights(v, free):
    return jnp.where(free, v, -jnp.inf)


def _one_if_free(v, free):
    return jnp.where(free, 1, 0).astype(jnp.int32)


def _gids(shape, n):
    pa, L = shape
    g = jnp.arange(pa * L, dtype=jnp.int32).reshape(pa, L)
    return jnp.where(g < n, g, I32MAX)


@jax.jit
def _mark_best(Aw: SpParMat, Aid: SpParMat, colfree: DistVec, wrow: DistVec):
    """Aid with vals = col id where (col free AND weight == row max) else
    I32MAX — the argmax-column selector for weighted proposals."""

    def body(wr, wc, wv, wn, ir, ic, iv, in_, freeb, rb):
        tw = Aw.local_tile(wr, wc, wv, wn)
        ti = Aid.local_tile(ir, ic, iv, in_)
        free, rmax = freeb[0], rb[0]
        fpad = jnp.concatenate([free, jnp.zeros((1,), free.dtype)])
        rpad = jnp.concatenate([rmax, jnp.full((1,), jnp.inf, rmax.dtype)])
        ci = jnp.minimum(tw.cols, free.shape[0])
        ri = jnp.minimum(tw.rows, rmax.shape[0])
        is_best = tw.valid_mask() & fpad[ci] & (tw.vals == rpad[ri])
        vals = jnp.where(is_best, ti.vals, I32MAX)
        return SpParMat._pack_tile(dataclasses.replace(ti, vals=vals))

    r, c, v, n = jax.shard_map(
        body,
        mesh=Aw.grid.mesh,
        in_specs=(TILE_SPEC,) * 8 + (P(COL_AXIS), P(ROW_AXIS)),
        out_specs=(TILE_SPEC,) * 4,
    )(
        Aw.rows, Aw.cols, Aw.vals, Aw.nnz,
        Aid.rows, Aid.cols, Aid.vals, Aid.nnz,
        colfree.blocks, wrow.blocks,
    )
    return dataclasses.replace(Aid, rows=r, cols=c, vals=v, nnz=n)


@partial(jax.jit, static_argnames=("heaviest",))
def _matching_round(
    Aid: SpParMat,
    Aw: SpParMat | None,
    mate_row,
    mate_col,
    only_deg1,
    heaviest: bool = False,
):
    """One propose/grant round → (mate_row', mate_col', newly matched count).

    mate_row: row-aligned int32 blocks (-1 = free); mate_col: col-aligned.
    ``only_deg1`` (traced bool) restricts proposers to rows with exactly one
    free-column neighbor — the Karp-Sipser rule.
    """
    grid = Aid.grid
    nr, nc = Aid.nrows, Aid.ncols

    colfree = DistVec(blocks=(mate_col < 0), length=nc, align="col", grid=grid)
    if heaviest:
        masked_w = Aw.dim_apply(colfree, _mask_free_weights, "cols")
        wcand = masked_w.reduce(MAX_MIN, "cols")  # row-aligned max weight
        cand = _mark_best(Aw, Aid, colfree, wcand.realign("row")).reduce(
            SELECT2ND_MIN, "cols"
        )
    else:
        masked_id = Aid.dim_apply(colfree, _mask_free_ids, "cols")
        cand = masked_id.reduce(SELECT2ND_MIN, "cols")  # min free col id

    deg_free = Aid.dim_apply(colfree, _one_if_free, "cols").reduce(
        PLUS_TIMES, "cols"
    )
    eligible = (mate_row < 0) & (cand.blocks != I32MAX)
    eligible = jnp.where(only_deg1, eligible & (deg_free.blocks == 1), eligible)

    row_gids = _gids(mate_row.shape, nr)
    prop_col = DistVec(
        blocks=jnp.where(eligible, cand.blocks, -1),
        length=nr, align="row", grid=grid,
    )
    prop_src = DistVec(
        blocks=jnp.where(eligible, row_gids, I32MAX),
        length=nr, align="row", grid=grid,
    )
    # Columns grant to the minimum proposing row.
    grant0 = DistVec(
        blocks=jnp.full(mate_col.shape, I32MAX, jnp.int32),
        length=nc, align="col", grid=grid,
    )
    granted = grant0.scatter_combine(SELECT2ND_MIN, idx=prop_col, src=prop_src)
    new_col = (granted.blocks != I32MAX) & (mate_col < 0)
    mate_col2 = jnp.where(new_col, granted.blocks, mate_col)

    # Rows learn their match via the reverse scatter.
    col_gids = _gids(mate_col.shape, nc)
    back_idx = DistVec(
        blocks=jnp.where(new_col, granted.blocks, -1),
        length=nc, align="col", grid=grid,
    )
    back_src = DistVec(
        blocks=jnp.where(new_col, col_gids, I32MAX),
        length=nc, align="col", grid=grid,
    )
    mrow0 = DistVec(
        blocks=jnp.full(mate_row.shape, I32MAX, jnp.int32),
        length=nr, align="row", grid=grid,
    )
    got = mrow0.scatter_combine(SELECT2ND_MIN, idx=back_idx, src=back_src)
    new_row = got.blocks != I32MAX
    mate_row2 = jnp.where(new_row, got.blocks, mate_row)
    return mate_row2, mate_col2, jnp.sum(new_col).astype(jnp.int32)


def maximal_matching(
    A: SpParMat, *, karp_sipser: bool = True, weighted: bool = False
) -> tuple[DistVec, DistVec]:
    """Maximal matching on A's nonzero pattern (rows = left, cols = right).

    Returns (mate_row, mate_col): row-/col-aligned int32 DistVecs with -1
    for unmatched. ``karp_sipser`` prioritizes degree-1 rows; ``weighted``
    proposes heaviest edges (the AWPM initialization). Reference:
    ``BPMaximalMatching.h``.
    """
    return _maximal_matching_rounds(
        A, karp_sipser=karp_sipser, weighted=weighted)[:2]


def _maximal_matching_rounds(A: SpParMat, *, karp_sipser=True, weighted=False):
    """``maximal_matching`` and, third, the rounds it ran: each one
    launch and one scalar read back."""
    grid = A.grid
    nr, nc = A.nrows, A.ncols
    Aid = _colid_matrix(A)
    Aw = A if weighted else None
    mate_row = DistVec.full(grid, nr, -1, jnp.int32, align="row").blocks
    mate_col = DistVec.full(grid, nc, -1, jnp.int32, align="col").blocks
    rounds = 0
    while True:
        nnew_total = 0
        if karp_sipser:
            mate_row, mate_col, nnew = _matching_round(
                Aid, Aw, mate_row, mate_col, jnp.bool_(True), heaviest=weighted
            )
            nnew_total += int(nnew)
            rounds += 1
        if nnew_total == 0:
            mate_row, mate_col, nnew = _matching_round(
                Aid, Aw, mate_row, mate_col, jnp.bool_(False), heaviest=weighted
            )
            nnew_total += int(nnew)
            rounds += 1
        if nnew_total == 0:
            break
    return (
        DistVec(blocks=mate_row, length=nr, align="row", grid=grid),
        DistVec(blocks=mate_col, length=nc, align="col", grid=grid),
        rounds,
    )


#: The ``jax.named_scope`` names of a matching job's program
#: (``_mcm_job_ell``), outermost first.  Trace-time metadata only: the
#: device trace's per-scope and per-phase times are read by these names
#: (docs/observability.md "Named scopes"), so a rename is a change of
#: yardstick.  ``push`` / ``sweep`` say how a round's step or a layer
#: was taken; under ``sweep`` an ``EllParMat``'s class loop sets its own
#: ``ell.bucket<i>`` / ``gather`` / ``fold`` / ``scatter_rows``.
MCM_SCOPES = (
    "mcm.init",  # the Karp-Sipser rounds, one ``while``
    "mcm.init.push",
    "mcm.init.sweep",
    "mcm.phase",  # the ``while`` whose iteration is one augmenting phase
    "mcm.bfs",  # a phase's alternating layers, one ``while``
    "mcm.bfs.push",
    "mcm.bfs.sweep",
    "mcm.chase",  # the candidates' chains claimed and checked
    "mcm.augment",  # the surviving paths flipped
)


#: Entries of a short list handled together: the candidate paths a
#: phase's chase walks (``_alternating_phase``), the granting columns a
#: round settles (``_karp_sipser``): the lanes of their subscripts and
#: scatters.  Static: it sizes them.
LIST_LANES = 1 << 14


def _short_list(mask, gids):
    """The ids ``gids`` (int32 blocks) where ``mask`` holds, as a list to
    go through ``LIST_LANES`` at a time: ``(chunk, chunks)``,
    ``chunk(k)`` the ``k``-th ``[lanes]`` of them ascending (I32MAX: no
    entry), ``chunks`` how many hold one.  What is listed is usually few
    beside the vector (a phase's candidates, a late round's grants), and
    on this chip a subscript or a scatter costs its indices, used or
    dropped: one sort brings the listed to the front, and what follows
    costs what they are."""
    lanes = min(LIST_LANES, gids.size)
    order = lax.sort(
        jnp.where(mask, gids, I32MAX).reshape(-1), is_stable=False)
    order = jnp.concatenate([order, jnp.full((lanes,), I32MAX, jnp.int32)])
    chunks = -(-jnp.sum(mask.astype(jnp.int32)) // lanes)
    return (lambda k: lax.dynamic_slice(order, (k * lanes,), (lanes,)),
            chunks)


def _alternating_phase(layer, grid, mate_row: DistVec, mate_col: DistVec,
                       tally):
    """One augmenting phase, entirely on device.

    Alternating-layer BFS from free rows: ``layer(frontier, unseen,
    tally) -> (reach, tally)`` is one layer, ``frontier`` the bool
    row-aligned blocks of the rows in it, ``unseen`` the bool col-aligned
    blocks of the columns no layer has reached, ``reach`` for every
    unseen column ONE adjacent frontier row (-1: none), which IS the
    parent assignment; which one is the layer's business (the smallest
    over COO tiles, the largest over ELL buckets: any deterministic
    parent gives shortest augmenting paths, and the cardinality of the
    maximum is unique).  ``tally`` is the layer's own carry (what it
    counts of its work), handed through.  The BFS stops
    at the first layer containing a free column; every free column found
    then traces its parent chain back in parallel (bounded while_loops of
    device gathers over a short list of them, ``LIST_LANES`` at a
    time), and vertex-disjointness is decided by WINNER
    SELECTION: each candidate path scatter-mins its path id onto every
    row it uses; a path survives iff it won all its rows.  The globally
    minimal surviving id always wins all of its rows, so a phase that
    finds any path augments at least one — no livelock.  Conflicting
    paths simply wait for a later phase (the reference's serial augment
    over its local queue has the same effect,
    BPMaximumMatching.cpp:156-188).

    Returns (mate_row', mate_col', n_augmented, layers, tally').
    """
    nr, nc = mate_row.length, mate_col.length
    mr, mc = mate_row, mate_col

    row_gids = DistVec.iota(grid, nr, align="row")
    col_gids = DistVec.iota(grid, nc, align="col")
    rows_ok = row_gids.blocks < nr
    cols_ok = col_gids.blocks < nc

    def vec(blocks, length, align):
        return DistVec(blocks=blocks, length=length, align=align, grid=grid)

    # --- alternating-layer BFS --------------------------------------------
    st0 = (
        (mr.blocks < 0) & rows_ok,  # frontier: the free rows
        jnp.full(mc.blocks.shape, -1, jnp.int32),  # col_parent
        jnp.zeros(mc.blocks.shape, bool),  # col_seen
        jnp.bool_(False),  # found a free column
        jnp.bool_(True),  # frontier nonempty
        jnp.int32(0),  # depth
        tally,
    )

    def bfs_cond(st):
        _, _, _, found, nonempty, depth, _ = st
        return (~found) & nonempty & (depth < nr + 2)

    def bfs_body(st):
        fr, col_parent, col_seen, _, _, depth, tally = st
        reach, tally = layer(fr, ~col_seen & cols_ok, tally)
        newc = (reach >= 0) & ~col_seen & cols_ok
        col_parent = jnp.where(newc, reach, col_parent)
        col_seen = col_seen | newc
        free_new = newc & (mc.blocks < 0)
        found = jnp.any(free_new)
        # next frontier: the rows matched to newly seen columns (a
        # matched row reads its own column: the mates are each other's
        # inverse, so this is the scatter of the columns' mates)
        hit = vec(newc.astype(jnp.int32), nc, "col").gather(
            vec(jnp.maximum(mr.blocks, 0), nr, "row")).blocks
        fr2 = rows_ok & (mr.blocks >= 0) & (hit != 0)
        return (fr2, col_parent, col_seen, found, jnp.any(fr2), depth + 1,
                tally)

    with jax.named_scope("mcm.bfs"):
        _, col_parent, col_seen, found, _, depth, tally = lax.while_loop(
            bfs_cond, bfs_body, st0
        )

    # --- parallel back-chase over the candidates, LIST_LANES at a time ----
    # A candidate is a free column the last layer found; its path id is
    # its own id.  The candidates are few beside the columns (hundreds to
    # thousands a phase on a Graph500 pattern of a million), so the chase
    # runs over a short list of them (``_short_list``): every step of a
    # chain is a subscript and a scatter of LIST_LANES entries into the
    # flat tables, not of one entry a column.  More candidates than lanes
    # go chunk by chunk; claims are complete before any chain is checked,
    # so the outcome is that of chasing them all at once.
    cand = found & col_seen & (mc.blocks < 0) & cols_ok
    shape_r, shape_c = mr.blocks.shape, mc.blocks.shape
    chunk, chunks = _short_list(cand, col_gids.blocks)
    parent_of = col_parent.reshape(-1)
    mate_of = mr.blocks.reshape(-1)
    nowhere = max(parent_of.size, mate_of.size)  # dropped by a scatter

    def chase(ids, step_fn, carry):
        """Walk the chains of the candidates ``ids`` ([lanes], I32MAX:
        none) together, <= depth+1 steps: ``step_fn(carry, cur, r,
        alive)`` at every (column, its parent row) of every chain."""
        alive = ids != I32MAX

        def cond(st):
            _, alive, step, _ = st
            return jnp.any(alive) & (step <= depth)

        def body(st):
            cur, alive, step, carry = st
            r = jnp.where(alive, parent_of[cur], -1)
            carry = step_fn(carry, cur, r, alive)
            nxt = mate_of[jnp.maximum(r, 0)]
            cont = alive & (r >= 0) & (nxt >= 0)
            return jnp.where(cont, nxt, cur), cont, step + 1, carry

        return lax.while_loop(
            cond, body, (jnp.where(alive, ids, 0), alive, jnp.int32(0),
                         carry))[3]

    # pass 1: claim rows (min path id wins each row)
    def claim(k, claims):
        ids = chunk(k)
        return chase(ids, lambda claims, cur, r, alive: claims.at[
            jnp.where(alive, r, nowhere)].min(ids, mode="drop"), claims)

    # pass 2: a path survives iff it won every row on its chain;
    # pass 3: the surviving (disjoint) paths are flipped in parallel
    def settle(k, st):
        upd_r, upd_c, n_aug = st
        ids = chunk(k)
        with jax.named_scope("mcm.chase"):
            survive = chase(
                ids, lambda ok, cur, r, alive: ok & jnp.where(
                    alive, claims[jnp.maximum(r, 0)] == ids, True),
                ids != I32MAX)

        def flip(upd, cur, r, alive):
            upd_r, upd_c = upd
            act = alive & survive & (r >= 0)
            return (
                upd_r.at[jnp.where(act, r, nowhere)].min(cur, mode="drop"),
                upd_c.at[jnp.where(act, cur, nowhere)].min(r, mode="drop"))

        with jax.named_scope("mcm.augment"):
            upd_r, upd_c = chase(ids, flip, (upd_r, upd_c))
        return upd_r, upd_c, n_aug + jnp.sum(survive.astype(jnp.int32))

    with jax.named_scope("mcm.chase"):
        claims = lax.fori_loop(
            0, chunks, claim, jnp.full((mate_of.size,), I32MAX, jnp.int32))
    upd_r, upd_c, n_aug = lax.fori_loop(0, chunks, settle, (
        jnp.full((mate_of.size,), I32MAX, jnp.int32),
        jnp.full((parent_of.size,), I32MAX, jnp.int32), jnp.int32(0)))
    upd_r, upd_c = upd_r.reshape(shape_r), upd_c.reshape(shape_c)
    mr2 = jnp.where(upd_r != I32MAX, upd_r, mr.blocks)
    mc2 = jnp.where(upd_c != I32MAX, upd_c, mc.blocks)
    return vec(mr2, nr, "row"), vec(mc2, nc, "col"), n_aug, depth, tally


@jax.jit
def _mcm_phase(AT: SpParMat, mate_row: DistVec, mate_col: DistVec):
    """``_alternating_phase`` over COO tiles: a layer is one
    ``dist_spmv(SELECT2ND_MIN, Aᵀ, frontier)``, a whole pass of the
    matrix whatever the frontier holds, and a column's parent the
    smallest adjacent frontier row (the host oracle's rule).  Returns
    (mate_row', mate_col', n_augmented): the ONLY host traffic per phase
    is the caller's scalar termination readback."""
    grid = AT.grid
    nr = AT.ncols  # AT is [nc, nr]
    row_gids = DistVec.iota(grid, nr, align="row").blocks

    def layer(frontier, _unseen, tally):
        reach = dist_spmv(SELECT2ND_MIN, AT, DistVec(
            blocks=jnp.where(frontier, row_gids, I32MAX), length=nr,
            align="row", grid=grid)).blocks
        return jnp.where(reach == I32MAX, -1, reach), tally

    return _alternating_phase(layer, grid, mate_row, mate_col, ())[:3]


def maximum_matching_device(
    A: SpParMat, init: tuple | None = None
) -> tuple[DistVec, DistVec]:
    """Maximum-cardinality matching with ON-DEVICE augmentation.

    Each phase is one jitted SPMD program (``_mcm_phase``); the host loop
    reads back a single scalar per phase for termination — no gathered
    pointer arrays, no per-step D2H (the host-loop prototype remains as
    ``maximum_matching(device=False)``, the validation oracle).
    ``mcm_job`` is this from the empty matching with its turns counted,
    and, over a ``BipartiteEll``, the same phases in one program.
    Reference: ``BPMaximumMatching.cpp:124-188``.
    """
    return _maximum_matching_phases(A, init)[:2]


def _maximum_matching_phases(A: SpParMat, init):
    """``maximum_matching_device`` and, third, the phases it ran (the
    one that augments nothing included): each one launch and one scalar
    read back."""
    mate_row, mate_col = init if init is not None else maximal_matching(A)
    AT = A.transpose().apply(ones_f32)
    phases = 0
    while True:
        mate_row, mate_col, n_aug = _mcm_phase(AT, mate_row, mate_col)
        phases += 1
        if int(n_aug) == 0:
            break
    return mate_row, mate_col, phases


def maximum_matching(
    A: SpParMat, init: tuple | None = None, *, device: bool = True
) -> tuple[DistVec, DistVec]:
    """Maximum-cardinality matching via augmenting-path phases.

    ``device=True`` (default): on-device phases, one scalar readback each
    (``maximum_matching_device``).  ``device=False``: the host-augmentation
    prototype (distributed structural sweep + serial host augment over
    gathered pointer arrays — the analog of the reference's serial augment
    over its locally-owned queue, BPMaximumMatching.cpp:156-188); kept as
    the validation oracle.
    """
    if device:
        return maximum_matching_device(A, init=init)
    grid = A.grid
    nr, nc = A.nrows, A.ncols
    mate_row, mate_col = init if init is not None else maximal_matching(A)
    mr = np.asarray(mate_row.to_global()).copy().astype(np.int64)
    mc = np.asarray(mate_col.to_global()).copy().astype(np.int64)
    AT = A.transpose().apply(ones_f32)
    # Host CSC adjacency for path reconstruction: O(deg) per column lookup
    # instead of an O(nnz) scan per reached column.
    ar, ac, _ = A.to_global_coo()
    order = np.argsort(ac, kind="stable")
    ar_sorted = ar[order]
    col_ptr = np.searchsorted(ac[order], np.arange(nc + 1))

    def col_neighbors(j):
        return ar_sorted[col_ptr[j] : col_ptr[j + 1]]

    while True:
        col_parent = np.full(nc, -1, np.int64)
        col_seen = np.zeros(nc, bool)
        frontier_rows = np.nonzero(mr < 0)[0]
        found_free_cols: np.ndarray = np.array([], np.int64)
        guard = 0
        while len(frontier_rows) and guard <= nc + 1:
            guard += 1
            fmask = np.zeros(nr, np.float32)
            fmask[frontier_rows] = 1.0
            fr = DistVec.from_global(grid, fmask, align="col", fill=0)
            reach = dist_spmv(PLUS_TIMES, AT, fr)  # length nc, row-aligned
            reached = (np.asarray(reach.to_global()) > 0) & ~col_seen
            newcols = np.nonzero(reached)[0]
            if len(newcols) == 0:
                break
            in_frontier = np.zeros(nr, bool)
            in_frontier[frontier_rows] = True
            for j in newcols:  # deterministic min adjacent frontier row
                nbrs = col_neighbors(j)
                col_parent[j] = nbrs[in_frontier[nbrs]].min()
            col_seen[newcols] = True
            free_new = newcols[mc[newcols] < 0]
            if len(free_new):
                found_free_cols = free_new
                break
            frontier_rows = mc[newcols]
        if len(found_free_cols) == 0:
            break
        used_rows: set[int] = set()
        augmented = 0
        for j in found_free_cols:
            path = []
            cj = int(j)
            ok = True
            while True:
                ri = int(col_parent[cj])
                if ri < 0 or ri in used_rows:
                    ok = False
                    break
                path.append((ri, cj))
                if mr[ri] < 0:
                    break
                cj = int(mr[ri])
            if not ok:
                continue
            for ri, _ in path:
                used_rows.add(ri)
            for ri, cj in path:
                mr[ri] = cj
                mc[cj] = ri
            augmented += 1
        if augmented == 0:
            break

    return (
        DistVec.from_global(grid, mr.astype(np.int32), align="row", fill=-1),
        DistVec.from_global(grid, mc.astype(np.int32), align="col", fill=-1),
    )


# --- the whole job: Karp-Sipser rounds, then phases -------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["A", "AT", "col_lists", "row_lists"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class BipartiteEll:
    """A bipartite pattern (rows one side, columns the other, NOT
    symmetrised) as ``mcm_job`` sweeps and walks it, both ways:

    ``A`` ``[nr, nc]`` and ``AT`` ``[nc, nr]``, the pattern and its
    transpose as ELL buckets (a sweep folds a ROW's list: a column of
    the pattern collects from its rows through ``AT``, a row from its
    columns through ``A``); ``col_lists`` / ``row_lists``, the CSC
    companions of ``A`` and of ``AT`` (``ellmat.build_csc_companion``:
    ``(indptr, rowidx)``), a column's rows and a row's columns as lists
    to walk.

    Rectangular and two-way, not the symmetric embedding ``[[0, A],
    [Aᵀ, 0]]`` of ``nr + nc`` vertices that a ``GraphEngine`` would load
    as it stands: the same slots and list entries are stored either way,
    but the embedding's every sweep gathers both directions' slots where
    a step needs one, its walks sort ``nr + nc`` columns where these
    sort one side's, and every vector of the job (mates, parents, the
    chase's lanes) is ``nr + nc`` long where one side's would do
    (PERF.md section 6, PR 54, has the two measured).  An engine loads
    one ELL and, without the BFS kind, no companion, so this comes from
    ``EllParMat.from_host_coo`` and ``build_csc_companion`` directly.
    """

    A: EllParMat
    AT: EllParMat
    col_lists: tuple
    row_lists: tuple

    @staticmethod
    def host_build(grid, rows, cols, nr: int, nc: int) -> dict:
        """HOST-ONLY half of ``from_host_coo`` (numpy in, numpy out; the
        COO holds each nonzero once): ``{"A": buckets, "AT": buckets,
        "col_lists": (indptr, rowidx), "row_lists": (indptr, rowidx)}``:
        the shapes a program is compiled for, with no device."""
        from ..parallel.ellmat import build_csc_companion_host

        rows, cols = np.asarray(rows), np.asarray(cols)
        ones = np.ones(len(rows), np.float32)
        return {
            "A": EllParMat.host_build(grid, rows, cols, ones, nr, nc),
            "AT": EllParMat.host_build(grid, cols, rows, ones, nc, nr),
            "col_lists": build_csc_companion_host(grid, rows, cols, nr, nc),
            "row_lists": build_csc_companion_host(grid, cols, rows, nc, nr),
        }

    @staticmethod
    def from_host(grid, host: dict, nr: int, nc: int) -> "BipartiteEll":
        """Upload ``host_build``'s arrays."""
        from ..parallel.ellmat import upload_csc_companion

        return BipartiteEll(
            A=EllParMat.from_host_buckets(grid, host["A"], nr, nc),
            AT=EllParMat.from_host_buckets(grid, host["AT"], nc, nr),
            col_lists=upload_csc_companion(grid, *host["col_lists"]),
            row_lists=upload_csc_companion(grid, *host["row_lists"]),
        )

    @staticmethod
    def from_host_coo(grid, rows, cols, nr: int, nc: int) -> "BipartiteEll":
        """Build from the host COO (each nonzero once) and upload: the
        load of this operand, under the span an engine's load has
        (``serve.load`` with ``bucket`` and ``upload`` inside; the
        lists' arrays go with the buckets')."""
        with obs.span("serve.load", nrows=nr, ncols=nc, nnz=len(rows)):
            with obs.span("bucket"):
                host = BipartiteEll.host_build(grid, rows, cols, nr, nc)
            with obs.span("upload") as upload:
                M = BipartiteEll.from_host(grid, host, nr, nc)
                upload.sync_on(M)
        return M


class _Way(NamedTuple):
    """One direction of the pattern, laid out for a loop ONCE before it
    (``ellmat.tile_lines``): ``E`` folds into its rows from its columns,
    the companion lists each column's rows, ``capacity`` is what a walk
    may hold (``models.bfs.push_capacity``: the served BFS's)."""

    name: str  # "A" | "AT": which of the operand's matrices ``E`` is
    E: EllParMat
    coldeg: jax.Array
    indptr: jax.Array
    rowidx: jax.Array
    capacity: int


def _way(name: str, E: EllParMat, companion) -> _Way:
    indptr, rowidx = companion
    return _Way(name, E, *tile_lines(
        E.grid, indptr[..., 1:] - indptr[..., :-1], indptr, rowidx),
        push_capacity(E))


class _Work(NamedTuple):
    """What a loop counts of its steps (a round's proposals, a round's
    free degrees, a phase's layer: each one ``_walk_or_sweep``)."""

    steps: jax.Array  # int32[2]: taken as a walk / as a sweep (LAYER_MODES)
    edges: jax.Array  # int32[pr, pc]: edges each tile's walks held
    sweeps: dict  # {"A" | "AT": int32[pr, pc, classes, 2]}, ``SWEEP_MODES``


#: ``models.mcm.layers{mode}`` / ``.init_steps{mode}``: how a step went.
LAYER_MODES = ("push", "pull")


def _no_work(M: BipartiteEll) -> _Work:
    grid = M.A.grid
    return _Work(
        steps=jnp.zeros((2,), jnp.int32),
        edges=jnp.zeros((grid.pr, grid.pc), jnp.int32),
        sweeps={
            k: jnp.zeros(
                (grid.pr, grid.pc, len(E.buckets), len(SWEEP_MODES)),
                jnp.int32)
            for k, E in (("A", M.A), ("AT", M.AT))},
    )


def _walk_or_sweep(way: _Way, inside, active, fold: str, scope: str,
                   work: _Work):
    """What every row of ``way.E`` collects from the columns ``inside``
    (bool blocks ``[p, L]`` over ``E``'s columns), under ``fold``
    (``ellmat.PUSH_FOLDS``): ``max`` the largest such column adjacent to
    the row, -1 for none, right on the rows of ``active`` (bool blocks
    over ``E``'s rows) at least; ``count`` how many are adjacent.
    ``(y, work')``.

    Chosen on the device, step by step, as a served BFS level is
    (``models.bfs._bfs_batch_tallied``): where those columns' lists hold
    at most ``way.capacity`` edges on every tile (``ell_frontier_fit``,
    one pass over the membership words), a walk of them in the companion
    (``ell_frontier_push`` at width 1), which costs what they hold; else
    the class sweep, which costs the matrix (``ell_frontier_sweep`` under
    the row mask for ``max``, the one-lane plus-times sweep for
    ``count``).  Both give the same ``y``."""
    E = way.E
    member = DistMultiVec(
        blocks=pack_lanes(inside[..., None]), length=E.ncols, align="row",
        grid=E.grid).realign("col").blocks
    fits, edges = ell_frontier_fit(E, way.coldeg, member, way.capacity)
    tally0 = jnp.zeros_like(work.sweeps[way.name])

    def walk(member, _active):
        with jax.named_scope(scope + ".push"):
            y, _ = ell_frontier_push(
                E, way.indptr, way.rowidx, member, 1, way.capacity, fold)
        return y[..., 0], tally0

    def sweep(member, active):
        with jax.named_scope(scope + ".sweep"):
            if fold == "max":
                y, tally = ell_frontier_sweep(E, member, active[..., None])
                return y[..., 0], tally
            x = DistVec(
                blocks=(member[..., 0] != 0).astype(jnp.float32),
                length=E.ncols, align="col", grid=E.grid)
            # exact: a count is under 2^24 (a tile's columns are)
            y = dist_spmv_ell(PLUS_TIMES, E, x).blocks.astype(jnp.int32)
            return y, tally0.at[..., 0].set(1)

    y, tally = lax.cond(fits, walk, sweep, member, active)
    return y, _Work(
        steps=work.steps + jnp.stack([fits, ~fits]).astype(jnp.int32),
        edges=work.edges + jnp.where(fits, edges, 0),
        sweeps=dict(
            work.sweeps, **{way.name: work.sweeps[way.name] + tally}),
    )


def _karp_sipser(M: BipartiteEll, to_cols: _Way, to_rows: _Way, deg):
    """The maximal matching a job starts from: ``(mate_row, mate_col,
    rounds, work)``, blocks; ``deg`` the rows' degrees (int32 blocks).

    ``maximal_matching``'s loop of rounds (a Karp-Sipser round, rows with
    ONE free column propose, while that matches anything, else a round
    in which every free row with a free column does; over when that
    matches nothing) as one ``while`` whose rounds cost their proposers.
    The rows' free degrees are a vector kept up to date (upstream's way),
    so a round is two steps of ``_walk_or_sweep``: the proposers' lists
    walked towards the columns, each free column granting the LARGEST
    proposer that reaches it; then the lists of the columns just matched
    walked back towards the rows with a count, which comes off the free
    degrees.  Two ties differ from ``maximal_matching``: a column takes
    its largest proposer, not its smallest (the walk's fold is the
    served BFS's max), and in the round of all free rows a row proposes
    to EVERY free column it has, not to its smallest, and of the columns
    that grant it takes the largest (the others stay free for the next
    round; a column that is granted is always matched, so the round that
    matches nothing still means no free row has a free column: the
    matching is maximal).  A Karp-Sipser round is unchanged (its
    proposers have one free column).  Another maximal matching, then,
    and possibly another count of phases after it; the cardinality of
    the maximum they reach is unique."""
    grid = M.A.grid
    nr, nc = M.A.nrows, M.A.ncols
    row_gids = DistVec.iota(grid, nr, align="row").blocks
    col_gids = DistVec.iota(grid, nc, align="col").blocks
    rows_ok, cols_ok = row_gids < nr, col_gids < nc

    KS, ALL, DONE = 0, 1, 2

    def cond(st):
        return st[3] != DONE

    def body(st):
        mr, mc, deg_free, mode, rounds, work = st
        free_c = (mc < 0) & cols_ok
        proposes = (mr < 0) & rows_ok & jnp.where(
            mode == KS, deg_free == 1, deg_free >= 1)
        # the largest proposer that reaches each free column
        reach, work = _walk_or_sweep(
            to_cols, proposes, free_c, "max", "mcm.init", work)
        granted = jnp.where(free_c, reach, -1)
        # the grants settled over the short list of the granting columns
        chunk, chunks = _short_list(granted >= 0, col_gids)
        granted_of = granted.reshape(-1)
        nowhere = max(mr.size, mc.size)  # dropped by a scatter

        def grants(k):
            ids = chunk(k)
            there = ids != I32MAX
            rows = granted_of[jnp.where(there, ids, 0)]
            return ids, there, rows

        def take(k, got):
            """a row takes the largest column that grants it ..."""
            ids, there, rows = grants(k)
            return got.at[jnp.where(there, rows, nowhere)].max(
                ids, mode="drop")

        got = lax.fori_loop(
            0, chunks, take, jnp.full((mr.size,), -1, jnp.int32))

        def settle(k, mates):
            """... and a column is matched where its grant was taken"""
            ids, there, rows = grants(k)
            won = there & (got[jnp.where(there, rows, 0)] == ids)
            return mates.at[jnp.where(won, ids, nowhere)].set(
                rows, mode="drop")

        mc2 = lax.fori_loop(0, chunks, settle, mc.reshape(-1)).reshape(
            mc.shape)
        taken = (mc2 >= 0) & (mc < 0)
        got = got.reshape(mr.shape)
        mr, mc = jnp.where(got >= 0, got, mr), mc2
        # the columns just matched come off their rows' free degrees
        gone, work = _walk_or_sweep(
            to_rows, taken, rows_ok, "count", "mcm.init", work)
        matched = jnp.any(taken)
        mode = jnp.where(matched, KS, jnp.where(mode == KS, ALL, DONE))
        return mr, mc, deg_free - gone, mode, rounds + 1, work

    mr, mc, _, _, rounds, work = lax.while_loop(cond, body, (
        jnp.full(row_gids.shape, -1, jnp.int32),
        jnp.full(col_gids.shape, -1, jnp.int32),
        deg, jnp.int32(KS), jnp.int32(0), _no_work(M)))
    return mr, mc, rounds, work


@jax.jit
def _mcm_job_ell(M: BipartiteEll, init=None):
    """A whole matching job over ELL buckets, one program: ``(mate_row,
    mate_col, counts)``, plain blocks and a dict of device scalars and
    tallies (``mcm_job`` reads them back with the mates).  ``init``
    (``(mate_row, mate_col)`` blocks of a matching of the pattern; the
    tests' way to a phase of their choosing) stands in for the
    Karp-Sipser rounds: a job has none."""
    grid = M.A.grid
    nr, nc = M.A.nrows, M.A.ncols

    def vec(blocks, length, align):
        return DistVec(blocks=blocks, length=length, align=align, grid=grid)

    with jax.named_scope("mcm.init"):
        to_cols = _way("AT", M.AT, M.row_lists)
        to_rows = _way("A", M.A, M.col_lists)
        # exact: a degree is under 2^24 (a tile's columns are)
        deg = M.A.reduce(PLUS_TIMES, "cols").blocks.astype(jnp.int32)
        if init is None:
            mr, mc, rounds, init_work = _karp_sipser(
                M, to_cols, to_rows, deg)
        else:
            mr, mc, rounds, init_work = *init, jnp.int32(0), _no_work(M)
        init_matched = jnp.sum((mc >= 0).astype(jnp.int32))

    def layer(frontier, unseen, work):
        # (a row without a nonzero reaches nothing: no column of a walk)
        return _walk_or_sweep(
            to_cols, frontier & (deg > 0), unseen, "max", "mcm.bfs", work)

    def cond(st):
        return st[2] != 0

    def body(st):
        mr, mc, _, phases, augmented, work = st
        mr, mc, n_aug, _, work = _alternating_phase(
            layer, grid, vec(mr, nr, "row"), vec(mc, nc, "col"), work)
        return (mr.blocks, mc.blocks, n_aug, phases + 1, augmented + n_aug,
                work)

    with jax.named_scope("mcm.phase"):
        mr, mc, _, phases, augmented, work = lax.while_loop(cond, body, (
            mr, mc, jnp.int32(1), jnp.int32(0), jnp.int32(0), _no_work(M)))
    return mr, mc, {
        "init_rounds": rounds, "init_matched": init_matched,
        "phases": phases, "augmented": augmented,
        "init": init_work, "bfs": work,
    }


class McmJob(NamedTuple):
    """What ``mcm_job`` returns, on the host."""

    mate_row: np.ndarray  # int32[nr]: a row's column, -1 unmatched
    mate_col: np.ndarray  # int32[nc]: a column's row, -1 unmatched
    cardinality: int
    phases: int  # augmenting phases, the one that found nothing included
    init_rounds: int  # rounds of the maximal matching it started from
    init_matched: int  # pairs that matching held
    host_turns: int  # launches the host waited on a scalar of, or the one


def mcm_job(M) -> McmJob:
    """Maximum cardinality matching of a bipartite pattern from the empty
    matching, upstream's ``bpmm`` (MCM-DIST, Azad & Buluç, IPDPS 2016):
    a Karp-Sipser maximal matching, then multi-source alternating BFS
    phases until one augments nothing (plain phases: no tree grafting,
    no pruning of finished trees).  Closed by the readback of both mate
    vectors.  ``M``'s type picks the path and nothing else does:

    an ``SpParMat``: ``maximal_matching`` and ``maximum_matching_device``,
    a launch and a scalar read back a round and a phase
    (``host_turns``), every round two whole reductions of the matrix
    and every layer one whole pass;

    a ``BipartiteEll``: ONE program (``_mcm_job_ell``; ``host_turns`` 1)
    with both loops on the device, in which a round's two steps and a
    phase's layer are each a walk of the lists that are live (the
    proposers', the just matched columns', the frontier rows') where
    those fit the served BFS's capacity, and a class sweep of the matrix
    where they do not (``_walk_or_sweep``).  Parents and grants are the
    LARGEST adjacent candidate there (``SELECT2ND_MAX``: the walk's
    fold), the smallest over an ``SpParMat``, and a round of all free
    rows proposes to every free column (``_karp_sipser``): the mates may
    differ between the two paths, and so may ``init_rounds`` and
    ``phases``; the cardinality cannot, being the maximum's.

    With telemetry on, the job is counted in ``models.mcm.*`` and its
    sweeps in the ELL family (``kind="mcm"``)."""
    counts = None
    if isinstance(M, BipartiteEll):
        mr, mc, counts = _mcm_job_ell(M)
        grid, nr, nc = M.A.grid, M.A.nrows, M.A.ncols
        mate_row = DistVec(blocks=mr, length=nr, align="row", grid=grid)
        mate_col = DistVec(blocks=mc, length=nc, align="col", grid=grid)
        counts = jax.device_get(counts)
        rounds, init_matched, phases, turns = (
            int(counts["init_rounds"]), int(counts["init_matched"]),
            int(counts["phases"]), 1)
    else:
        mate_row, mate_col, rounds = _maximal_matching_rounds(M)
        init_matched = int(jnp.sum(mate_col.blocks >= 0))
        mate_row, mate_col, phases = _maximum_matching_phases(
            M, (mate_row, mate_col))
        turns = rounds + phases
    out_row = np.asarray(mate_row.to_global())  # the barrier
    out_col = np.asarray(mate_col.to_global())
    cardinality = int((out_col >= 0).sum())
    if obs.ENABLED:
        if counts is not None:
            # no warm-up of its own: as ``models/cc.py:fastsv``, the
            # first traced call publishes the program's op names AFTER
            # the call
            obs.opnames.publish_once(
                (_mcm_job_ell.__name__, M.A.grid,
                 tuple(a.shape for a in jax.tree_util.tree_leaves(M))),
                lambda: _mcm_job_ell.lower(M).compile().as_text(),
            )
            _count_work(M, counts)
        obs.count("models.mcm.jobs")
        obs.count("models.mcm.init_rounds", rounds)
        obs.count("models.mcm.init_matched", init_matched)
        obs.count("models.mcm.phases", phases)
        obs.count("models.mcm.augmented", cardinality - init_matched)
        obs.count("models.mcm.host_turns", turns)
    return McmJob(out_row, out_col, cardinality, phases, rounds,
                  init_matched, turns)


def _count_work(M: BipartiteEll, counts: dict) -> None:
    """A job's steps into ``models.mcm.init_steps{mode}`` /
    ``.layers{mode}`` / ``.push_edges`` (the busiest tile's) and its
    sweeps into the ELL family, ``kind="mcm"``, ``width=1``
    (``ellmat.count_sweep_work``); the job is one of ``ell.batches``."""
    for name, work in (("models.mcm.init_steps", counts["init"]),
                       ("models.mcm.layers", counts["bfs"])):
        for mode, steps in zip(LAYER_MODES, np.asarray(work.steps)):
            obs.count(name, int(steps), mode=mode)
        obs.count("models.mcm.push_edges", int(np.max(work.edges)))
        for which, E in (("A", M.A), ("AT", M.AT)):
            count_sweep_work(
                "mcm", 1, work.sweeps[which], class_slots(E), way=which)
    obs.count("ell.batches", 1, kind="mcm", width=1)


def awpm(A: SpParMat) -> tuple[DistVec, DistVec]:
    """Approximate-weight perfect matching: heaviest-edge Karp-Sipser
    initialization + cardinality augmentation (the composition of the
    reference's AWPM driver, ``ApproxWeightPerfectMatching.h``)."""
    init = maximal_matching(A, karp_sipser=True, weighted=True)
    return maximum_matching(A, init=init)


# --- host validation helpers (tests / drivers) ------------------------------


def matching_weight(A_dense, mate_row) -> float:
    mr = np.asarray(mate_row)
    return float(
        sum(np.asarray(A_dense)[i, j] for i, j in enumerate(mr) if j >= 0)
    )


def is_valid_matching(A_dense, mate_row, mate_col) -> bool:
    mr, mc = np.asarray(mate_row), np.asarray(mate_col)
    cols_used = [j for j in mr if j >= 0]
    if len(cols_used) != len(set(cols_used)):
        return False
    for i, j in enumerate(mr):
        if j >= 0 and (not A_dense[i, j] or mc[j] != i):
            return False
    return all(i < 0 or mr[i] == j for j, i in enumerate(mc))


def is_maximal(A_dense, mate_row, mate_col) -> bool:
    mr, mc = np.asarray(mate_row), np.asarray(mate_col)
    A_dense = np.asarray(A_dense)
    for i in range(len(mr)):
        if mr[i] < 0:
            for j in np.nonzero(A_dense[i])[0]:
                if mc[j] < 0:
                    return False
    return True
