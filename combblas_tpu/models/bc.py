"""Betweenness centrality — batched Brandes (≈ Applications/BetwCent.cpp).

The reference runs BFS from ``batchSize`` roots simultaneously by making the
frontier a sparse n × batch MATRIX: each forward level is one SpGEMM
(``PSpGEMM<PTBOOLINT>``, BetwCent.cpp:179-218), path counts accumulate into a
``DenseParMat``, and the backward (dependency) sweep re-walks the stored
level fringes with elementwise rescales. This is parallelism strategy #7 of
SURVEY §2.3 — batch parallelism over sources — and it maps perfectly to the
TPU: the batch dimension widens every kernel, feeding the MXU/VPU lanes.

Forward, per level d (host loop, like the reference's; orientation:
A[i,j] != 0 is edge j→i, the BFS convention, so path counts PULL from
predecessors via A and dependencies pull from successors via Aᵀ):
    fringe ← A ⊗ fringe             (SUMMA on the n × batch fringe)
    fringe ← fringe .!(nsp > 0)     (drop already-settled vertices)
    nsp    ← nsp + fringe           (dense accumulate of path counts)
Backward (Brandes dependency):
    w      ← fringe_d .* (1 + delta)/nsp     (dense-indexed rescale)
    contrib← Aᵀ ⊗ w
    delta  ← delta + (contrib .* fringe_{d-1}) * nsp_{d-1}
    bc     ← bc + Σ_batch delta

``bc_batch_dense`` is the one-launch redesign: dense [n, W] level/path
lanes, both sweeps under lax control flow, zero readbacks.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..semiring import PLUS_TIMES
from ..parallel.dense import DenseParMat
from ..parallel.grid import Grid
from ..parallel.spgemm import spgemm
from ..parallel.spmat import SpParMat
from ..parallel.vec import DistVec


def _keep_unsettled(sval, nsp_val):
    return nsp_val == 0


def _replace_with_dense(sval, dval):
    return dval


def _mul_combine(a, b):
    return a * b


def _sources_fringe(grid: Grid, sources, n: int, dtype) -> SpParMat:
    """n × batch selector: column k starts at source_k with 1 path."""
    sources = np.asarray(sources, dtype=np.int64)
    return SpParMat.from_global_coo(
        grid, sources, np.arange(len(sources)), np.ones(len(sources), dtype),
        n, len(sources),
    )


def bc_batch(A: SpParMat, sources, AT: SpParMat | None = None) -> DistVec:
    """Partial BC scores from one batch of source vertices (row-aligned
    float vector of dependency sums; endpoints excluded per Brandes).

    ``AT`` lets multi-batch callers hoist the transpose (a full distributed
    tile exchange) out of the batch loop.
    """
    grid = A.grid
    n = A.nrows
    if AT is None:
        AT = A.transpose()
    fringe = _sources_fringe(grid, sources, n, np.dtype(A.dtype))
    nsp = DenseParMat.zeros(grid, n, len(np.asarray(sources)), A.dtype)
    nsp = nsp.add_spmat(fringe)

    levels: list[SpParMat] = [fringe]
    # Forward sweep (host loop: depth is data-dependent, as in the
    # reference's while(fringe.getnnz() > 0), BetwCent.cpp:179).
    # Orientation: A[i,j] != 0 is edge j->i (the BFS convention), so path
    # counts PULL from predecessors via A; the backward dependency sweep
    # pulls from successors via AT. (Round-2 had these swapped — invisible
    # on symmetric graphs, wrong on directed ones; caught by the
    # bc_batch_dense cross-check against textbook Brandes.)
    while True:
        fringe = spgemm(PLUS_TIMES, A, fringe)
        fringe = nsp.filter_spmat(fringe, _keep_unsettled)
        if int(fringe.getnnz()) == 0:
            break
        nsp = nsp.add_spmat(fringe)
        levels.append(fringe)

    delta = DenseParMat.zeros(grid, n, nsp.ncols, A.dtype)
    # Backward dependency sweep (BetwCent.cpp:207-218): per Brandes,
    # delta[v] = Σ_{succ w} (nsp[v]/nsp[w]) (1 + delta[w]); on level-d
    # structure, w carries (1+delta)/nsp, the product A⊗w propagates to the
    # d-1 fringe, and the fringe's own values supply the nsp[v] factor.
    for d in range(len(levels) - 1, 0, -1):
        ratio = delta.ewise(nsp, _one_plus_a_over_b)
        w = ratio.scale_spmat(levels[d], _replace_with_dense)
        contrib = spgemm(PLUS_TIMES, AT, w)
        upd = contrib.ewise_mult(levels[d - 1], combine=_mul_combine)
        delta = delta.add_spmat(upd)
    total = delta.reduce(PLUS_TIMES, "cols")
    # Brandes excludes the source's own accumulated dependency (bc[w] only
    # sums over w != s): subtract delta at each batch's (source_k, k) slot.
    src_delta = delta.scale_spmat(levels[0], _replace_with_dense)
    correction = src_delta.reduce(PLUS_TIMES, "cols")
    return total.ewise(correction, jnp.subtract)


def _one_plus_a_over_b(delta_b, nsp_b):
    return jnp.where(nsp_b > 0, (1.0 + delta_b) / jnp.maximum(nsp_b, 1e-30), 0.0)


def betweenness_centrality(
    A: SpParMat,
    batch_size: int | None = None,
    sources=None,
    normalize: bool = False,
) -> DistVec:
    """Exact (all-sources) or sampled BC.

    ``sources`` defaults to all vertices, processed in batches of
    ``batch_size`` (default: one batch). For undirected graphs each pair is
    counted twice — pass ``normalize=True`` to halve, matching the usual
    undirected convention.
    """
    n = A.nrows
    srcs = np.arange(n) if sources is None else np.asarray(sources)
    if len(srcs) == 0:
        return DistVec.full(A.grid, n, 0, A.dtype, align="row")
    bs = batch_size or len(srcs)
    AT = A.transpose()
    acc = None
    for s in range(0, len(srcs), bs):
        part = bc_batch(A, srcs[s : s + bs], AT=AT)
        acc = part if acc is None else acc.ewise(part, jnp.add)
    if normalize:
        acc = acc.apply(lambda b: b * 0.5)
    return acc


#: The ``jax.named_scope`` names of the dense batch program
#: (``_bc_batch_lanes``), outermost first; inside ``bc.forward`` and
#: ``bc.backward`` the sweep's own ``ell.bucket<i>`` / ``gather`` /
#: ``fold`` / ``scatter_rows`` and ``vec.realign``.  Trace-time metadata
#: only: the device trace's per-scope and per-sweep times are read by
#: these names (docs/observability.md "Named scopes").
BC_SCOPES = (
    "bc.init",
    "bc.forward",  # the whole while loop; one iteration = one BFS level
    "bc.backward",  # the whole loop; one iteration = one level back
    "bc.finish",
)

#: What ``_bc_batch_lanes``' ``int32[2]`` of ELL sweeps counts, in order:
#: one forward sweep a BFS level, one backward sweep a level back; and the
#: first axis of its ``int32[2, pr, pc, classes, 2]`` tally of degree-class
#: sweeps (the last is ``ellmat.SWEEP_MODES``).
BC_PHASES = ("forward", "backward")


def bc_batch_dense(E, ET, sources, max_depth: int | None = None):
    """Eager wrapper over ``_bc_batch_dense_impl`` (plain-outputs law)."""
    total = _bc_batch_dense_impl(E, ET, sources, max_depth=max_depth)
    return DistVec(
        blocks=total, length=E.nrows, align="row", grid=E.grid
    )


def bc_batch_dense_lanes(E, ET, sources, max_depth: int | None = None):
    """Per-lane Brandes dependencies: the [n, W] delta matrix BEFORE the
    cross-lane sum — lane k is the single-source dependency vector of
    ``sources[k]`` (what a serve request for one root wants back).
    ``models.PAD_ROOT`` source slots yield all-zero lanes. Summing the
    lanes reproduces ``bc_batch_dense`` exactly.
    """
    from ..parallel.vec import DistMultiVec

    delta = _bc_batch_dense_impl(
        E, ET, sources, max_depth=max_depth, per_lane=True
    )
    return DistMultiVec(
        blocks=delta, length=E.nrows, align="row", grid=E.grid
    )


@partial(jax.jit, static_argnames=("max_depth", "per_lane"))
def _bc_batch_dense_impl(E, ET, sources, max_depth: int | None = None,
                         per_lane: bool = False):
    """``_bc_batch_lanes`` without its counts: the [pr, lr, W] per-lane
    dependencies (``per_lane=True``) or their sum over the lanes, the
    row-aligned partial BC blocks of these W sources."""
    delta = _bc_batch_lanes(E, ET, sources, max_depth)[0]
    if per_lane:
        return delta
    with jax.named_scope("bc.finish"):
        return jnp.sum(delta, axis=-1)


def _bc_batch_lanes(E, ET, sources, max_depth: int | None):
    """Batched Brandes in ONE compiled program over dense [n, W] state.

    The host-loop ``bc_batch`` mirrors the reference's
    ``while(fringe.getnnz())`` shape (BetwCent.cpp:179) — per-level SpGEMM
    sizing readbacks, which are launch-poison on the target chip. This
    variant is the TPU-native redesign: levels and path counts live as
    dense [n, W] lanes (the batched-BFS state layout), every sweep step is
    one multi-lane ELL SpMV, and both sweeps run under ``lax`` control
    flow — zero device→host readbacks.

    Both loops hand the sweep the row mask they apply to its result
    anyway (``ellmat.ell_masked_multi_sweep``, the served BFS plan's
    sweep): forward, the rows no lane with a frontier has reached yet;
    backward at ``d``, the rows AT level ``d - 1``.  Each tile then
    skips, on the device, every degree class none of whose rows the
    level can change, and every class's gather table is built in the
    branch that gathers from it.  A skipped class could only have added
    to rows the mask drops, so the answers are bit for bit those of the
    same program with every class swept.

    ``E``: adjacency with entry (i, j) = edge j→i (the BFS gather
    orientation); ``ET``: its transpose (pass the same EllParMat for
    symmetric graphs). ``sources``: [W] int32. Returns ``(delta,
    depth, sweeps, tally)``: the row-aligned PLAIN [pr, lr, W]
    per-lane dependencies (lane k is Brandes' delta of ``sources[k]``,
    endpoints excluded), the number of BFS levels that hold a vertex in
    the deepest lane (the roots' own level counted), the ``int32[2]``
    count of ELL sweeps the two loops ran, by ``BC_PHASES`` (forward: one
    a level, the last of which finds nothing unless ``max_depth`` cut the
    loop short; backward: one a level but the roots' and their
    neighbours'), and the ``int32[2, pr, pc, classes, 2]`` tally of what
    those sweeps did in every tile, class by class: ``BC_PHASES`` first,
    ``ellmat.SWEEP_MODES`` last (the forward loop sweeps ``E``, the
    backward one ``ET``; where the two hold different numbers of degree
    classes the shorter tally is padded with classes never swept).
    Not jitted itself: the served plan (``engine._build_plan``) traces it
    inside its own program and hands each lane back to its request, the
    depth as ``batch_niter``.
    """
    from ..parallel.ellmat import SWEEP_MODES, ell_masked_multi_sweep
    from ..parallel.vec import DistMultiVec
    from . import PAD_ROOT

    grid = E.grid
    n = E.nrows
    D = max_depth if max_depth is not None else n

    def mk(blocks):
        return DistMultiVec(blocks=blocks, length=n, align="row", grid=grid)

    def sweep(M, x, row_active):
        """``M ⊗ x`` where ``row_active`` keeps it, and the tile tally."""
        y, tally = ell_masked_multi_sweep(
            PLUS_TIMES, M, mk(x), mk(row_active))
        return y.blocks, tally

    with jax.named_scope("bc.init"):
        gids = DistVec.iota(grid, n, jnp.int32, align="row").blocks  # [pr, lr]
        # models.PAD_ROOT lanes are inert (all-zero dependencies — the
        # serve batcher's lane padding). The iota gid table pads with ids
        # >= n so PAD_ROOT can never match, but the explicit guard keeps
        # the contract independent of the gid-table padding convention
        # (the -1-padded _global_ids tables WOULD match).
        live = sources[None, None, :] != PAD_ROOT
        is_src = (gids[..., None] == sources[None, None, :]) & live
        lvl0 = jnp.where(is_src, 0, -1).astype(jnp.int32)
        nsp0 = is_src.astype(E.dtype)
        # per tile and class, as the sweep hands it up: a collective
        # accumulated inside a loop costs the loop its op_name

        def tally0(M):
            return jnp.zeros(
                (grid.pr, grid.pc, len(M.buckets), len(SWEEP_MODES)),
                jnp.int32)

    def fcond(st):
        d, _, _, active, _ = st
        return active & (d < D)

    def fstep(st):
        d, lvl, nsp, _, tally = st
        frontier = jnp.where(lvl == d, nsp, 0)
        arriving, swept = sweep(E, frontier, lvl < 0)
        new = (arriving > 0) & (lvl < 0)
        lvl = jnp.where(new, d + 1, lvl)
        nsp = nsp + jnp.where(new, arriving, 0)
        return d + 1, lvl, nsp, jnp.any(new), tally + swept

    # the whole loop, condition included, is one scope: a forward sweep
    # is one iteration of it in the device trace
    with jax.named_scope("bc.forward"):
        depth, lvl, nsp, still_active, ftally = jax.lax.while_loop(
            fcond, fstep, (jnp.int32(0), lvl0, nsp0, jnp.bool_(True), tally0(E))
        )

    # Backward dependency sweep: d = depth ... 2; every level-(d) vertex
    # w exports (1+delta[w])/nsp[w]; level-(d-1) predecessors v collect it
    # along their out-edges and scale by nsp[v]. Starting at d = depth
    # (one past the last level on natural exit — a no-op there) keeps the
    # deepest level's exports when the max_depth bound cut the forward
    # sweep short; the loop bound is the TRACED depth, so only the real
    # levels run (fori_loop lowers a traced bound to a while_loop).
    def bstep(k, st):
        delta, tally = st
        d = depth - k
        wmask = (lvl == d) & (nsp > 0)
        w = jnp.where(
            wmask, (1.0 + delta) / jnp.maximum(nsp, 1e-30), 0
        ).astype(E.dtype)
        collected, swept = sweep(ET, w, lvl == d - 1)
        upd = jnp.where(lvl == d - 1, collected * nsp, 0)
        return delta + upd, tally + swept

    with jax.named_scope("bc.backward"):
        # on natural exit level `depth` is empty (the last step found
        # nothing) — skip its guaranteed no-op SpMV; when the max_depth
        # bound cut the sweep short (still_active), level `depth` is real
        start = jnp.where(still_active, 0, 1)
        # d = 1 is not run: the one level it updates is the roots' own
        # entries, which bc.finish zeroes (a root with no edge has
        # depth 1: the bounds cross and nothing runs)
        delta, btally = jax.lax.fori_loop(
            start, depth - 1, bstep, (jnp.zeros_like(nsp0), tally0(ET))
        )
    with jax.named_scope("bc.finish"):
        # endpoints excluded: zero each lane's own source slot
        delta = jnp.where(is_src, 0, delta)
        # levels that hold a vertex: 0 .. depth - 1, and level `depth`
        # too where the bound stopped a sweep that was still finding
        levels = depth + still_active.astype(jnp.int32)
        # iterations of the two loops above, as they ran
        sweeps = jnp.stack(
            [depth, jnp.maximum(depth - 1 - start, 0)]).astype(jnp.int32)
        classes = max(len(E.buckets), len(ET.buckets))
        tally = jnp.stack([
            jnp.pad(t, ((0, 0), (0, 0), (0, classes - t.shape[2]), (0, 0)))
            for t in (ftally, btally)
        ])
    return delta, levels, sweeps, tally
