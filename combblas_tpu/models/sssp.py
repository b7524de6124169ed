"""Single-source shortest paths — Bellman-Ford over MIN_PLUS (≈ SSSP.cpp).

The reference iterates ``SpMV<MinPlusSRing>`` until the distance vector
stops improving (``Applications/SSSP.cpp`` main loop).  Identical here: the
tropical semiring SpMV relaxes every edge each round; the loop is a
``lax.while_loop`` fixed point, bounded by n rounds (longest possible
shortest path), so one compiled program covers any source.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..semiring import MIN_PLUS
from ..parallel.spmat import SpParMat
from ..parallel.spmv import dist_spmv
from ..parallel.vec import DistVec


def sssp(A: SpParMat, source) -> tuple[DistVec, jax.Array]:
    """Eager wrapper over ``_sssp_impl`` (plain-outputs law,
    round-5 notes; ROADMAP D14)."""
    blocks, niter = _sssp_impl(A, source)
    return (
        DistVec(blocks=blocks, length=A.nrows, align="row", grid=A.grid),
        niter,
    )


@jax.jit
def _sssp_impl(A: SpParMat, source):
    """Distances from ``source``; unreachable vertices hold +inf.

    A[i, j] = w is the weight of edge j -> i (same gather orientation as
    BFS); weights must be non-negative for meaningful results (Bellman-Ford
    itself tolerates negatives but the fixed-point bound assumes no negative
    cycles).  Returns (dist row-aligned float DistVec, iterations).
    """
    grid = A.grid
    n = A.nrows
    dtype = A.dtype
    inf = MIN_PLUS.zero(dtype)

    gids = DistVec.iota(grid, n, jnp.int32, align="row").blocks
    d0 = jnp.where(gids == source, jnp.zeros((), dtype), inf)

    def mk(blocks):
        return DistVec(blocks=blocks, length=n, align="row", grid=grid)

    def cond(state):
        _, changed, it = state
        return changed & (it < n)

    def step(state):
        db, _, it = state
        d = mk(db)
        relaxed = dist_spmv(MIN_PLUS, A, d.realign("col"))
        nb = jnp.minimum(db, relaxed.blocks)
        return nb, jnp.any(nb != db), it + 1

    db, _, niter = jax.lax.while_loop(
        cond, step, (d0, jnp.bool_(True), jnp.int32(0))
    )
    return db, niter


#: The ``jax.named_scope`` names of the served batch program
#: (``_sssp_batch_impl``), outermost first; inside ``sssp.round`` and
#: ``sssp.parents`` the sweep's own ``ell.bucket<i>`` / ``gather`` /
#: ``fold`` / ``scatter_rows`` and ``vec.realign``, and inside
#: ``sssp.round`` (a masked sweep since PR 33) each class's test for an
#: active row too, under its bare ``ell.bucket<i>``, and ``ell.reduce``.
#: Trace-time metadata only: the device trace's per-scope and per-round
#: times are read by these names (docs/observability.md "Named scopes").
SSSP_SCOPES = (
    "sssp.init",
    "sssp.round",  # the whole while loop; one iteration = one round
    "sssp.parents",  # the one sweep after the fixed point
)


def sssp_batch(E, sources):
    """Eager wrapper over ``_sssp_batch_impl`` (plain-outputs law):
    ``(dist, parents, rounds)``, the first two row-aligned
    ``DistMultiVec``s."""
    from ..parallel.vec import DistMultiVec

    dist, parents, niter, _ = _sssp_batch_impl(E, sources)

    def mk(blocks):
        return DistMultiVec(
            blocks=blocks, length=E.nrows, align="row", grid=E.grid
        )

    return mk(dist), mk(parents), niter


@jax.jit
def _sssp_batch_impl(E, sources):
    """Multi-source Bellman-Ford: Graph500 kernel 3's answer for W
    sources in ONE program.

    ``E``: weighted EllParMat (entry (i,j) = w(j->i), non-negative).
    ``sources``: [W] int32. Returns row-aligned PLAIN [pr, lr, W] blocks
    (the wrapper rebuilds the DistMultiVecs) of distances (+inf where
    unreachable) and of shortest-path parents (a root its own, -1 where
    unreachable), the round count, the round that changed nothing
    included, and the rounds' ``int32[pr, pc, classes, 2]`` tally of each
    tile's and degree class's sweeps by ``ellmat.SWEEP_MODES``.

    The multi-root amortization of the batched BFS applied to SSSP: the
    chip's gather cost is per-INDEX with payload lanes nearly free, so W
    Bellman-Ford chains advance for ~the cost of one (compare the
    single-source loop above, which pays the full gather per source).
    After the fixed point one more sweep picks each reached row's parent
    (``_ell_minplus_parents``): a neighbour ``j`` with ``d[j] + w ==
    d[row]`` closes a shortest path; the pick is the max id among the
    strictly nearer of them, and for a row that has none, among those as
    near that settled in an earlier round, which the loop records (zero
    and absorbed weights cannot close a cycle).

    A round hands the sweep the rows it can still lower
    (``ellmat.ell_masked_multi_sweep``, the served BFS and BC plans'
    sweep): with non-negative weights a distance falls in round k only
    through a neighbour that fell in round k - 1 (round k - 1 relaxed
    it over every other), so it ends above that neighbour's, and
    entries at or under the lane's ``floor``, the smallest distance
    round k - 1 lowered, are left alone.  Hubs hold a lane's smallest
    distances, so the widest degree classes go idle first, and each
    tile skips, on the device, a class with no such row.  ``min``
    neither rounds nor orders: the answers are bit for bit those of
    the same program with every class swept.
    Reference: ``Applications/SSSP`` role; the reference has no batched
    variant — this is TPU-native surface.
    """
    from ..parallel.ellmat import (
        SWEEP_MODES, _ell_minplus_parents, ell_masked_multi_sweep)
    from ..parallel.vec import DistMultiVec
    from . import PAD_ROOT

    grid = E.grid
    n = E.nrows
    dtype = E.dtype
    inf = MIN_PLUS.zero(dtype)

    def mk(blocks):
        return DistMultiVec(blocks=blocks, length=n, align="row", grid=grid)

    with jax.named_scope("sssp.init"):
        gids = DistVec.iota(grid, n, jnp.int32, align="row").blocks  # [pr, lr]
        src = sources.astype(jnp.int32)[None, None, :]
        # models.PAD_ROOT lanes are inert padding (all-inf distances,
        # all -1 parents: the serve batcher's lane padding); same guard
        # as _bfs_batch_impl
        is_root = (gids[..., None] == src) & (src != PAD_ROOT)
        d0 = jnp.where(is_root, jnp.zeros((), dtype), inf)
        # per tile and class, as the sweep hands it up: a collective
        # accumulated inside a loop costs the loop its op_name
        tally0 = jnp.zeros(
            (grid.pr, grid.pc, len(E.buckets), len(SWEEP_MODES)), jnp.int32)

    def cond(state):
        _, _, changed, it, _ = state
        return changed & (it < n)

    def step(state):
        db, settled, _, it, tally = state
        # the smallest distance the round before lowered, by lane (+inf
        # where it lowered none: a finished or PAD_ROOT lane; at round 0
        # every ``settled`` is 0 and only the roots are finite: their
        # 0).  Only an entry ABOVE it can fall now, and only because no
        # weight is negative (the contract above): a negative edge
        # would lower entries this mask leaves out.
        floor = jnp.min(jnp.where(settled == it, db, inf), axis=(0, 1))
        relaxed, swept = ell_masked_multi_sweep(
            MIN_PLUS, E, mk(db), mk(db > floor))
        nb = jnp.minimum(db, relaxed.blocks)
        lowered = nb != db
        # the round that last lowered each distance: what the parents
        # pass orders equal distances by
        settled = jnp.where(lowered, it + 1, settled)
        return nb, settled, jnp.any(lowered), it + 1, tally + swept

    # the whole loop, condition included, is one scope: a round is one
    # iteration of it in the device trace
    with jax.named_scope("sssp.round"):
        db, settled, _, niter, tally = jax.lax.while_loop(
            cond, step,
            (d0, jnp.zeros(d0.shape, jnp.int32), jnp.bool_(True),
             jnp.int32(0), tally0),
        )

    with jax.named_scope("sssp.parents"):
        parents = _ell_minplus_parents(E, db, settled)
        # roots are their own parents; unreached rows stay -1
        parents = jnp.where(is_root, src, parents)
        parents = jnp.where(db < inf, parents, -1)
    return db, parents, niter, tally
