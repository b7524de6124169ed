"""Connected components — FastSV (≈ Applications/FastSV.cpp/.h).

The reference's FastSV (Zhang, Azad, Hu; SIAM PP'20 implementation at
``Applications/FastSV.h``) iterates three label-lowering rules until the
parent vector stabilizes, each expressed in CombBLAS as a
``SpMV<Select2ndMinSR>`` over grandparent labels plus scatter-assign
(``FastSV.h:347-359`` SpMV on grandparents, ``FastSV.h:68-146``
Assign/ReduceAssign):

  1. stochastic hooking : f[f[i]] <- min(f[f[i]], u[i])
  2. aggressive hooking : f[i]    <- min(f[i],    u[i])
  3. shortcutting       : f[i]    <- min(f[i],    f[f[i]])

with ``u[i] = min over neighbors j of gf[j]`` and ``gf = f[f]``.

TPU-native expression: ``u`` is one semiring SpMV (SELECT2ND_MIN) over the
mesh; hooking is ``DistVec.scatter_combine`` (segment-min); the whole loop is
a ``lax.while_loop`` with a fixed-point convergence test — no host round
trips, the entire CC run is one XLA program.  The matrix is an ``SpParMat``
(COO tiles) or an ``EllParMat`` (what a loaded ``GraphEngine`` holds as
``engine.E``): the argument's type picks the sweep and nothing else differs.

``lacc`` below is a real implementation of LACC (``Applications/CC.h``,
Azad-Buluç IPDPS'19) — the star-hooking algorithm the reference's ctest
suite exercises — not an alias: conditional/unconditional star hooking,
star tracking, and shortcutting, each phase a dense vectorized step so the
whole run is one XLA program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..semiring import SELECT2ND_MIN
from ..parallel.ellmat import (
    SWEEP_MODES, EllParMat, class_slots, count_sweep_work,
)
from ..parallel.spmat import SpParMat
from ..parallel.spmv import dist_spmv
from ..parallel.vec import DistVec

#: The ``jax.named_scope`` names of the FastSV program (``_fastsv``),
#: outermost first; inside ``cc.spmv`` an ``EllParMat``'s sweep sets its
#: own ``ell.bucket<i>`` / ``gather`` / ``fold`` / ``scatter_rows``.
#: Trace-time metadata only: the device trace's per-scope and per-round
#: times are read by these names (docs/observability.md "Named scopes"),
#: so a rename is a change of yardstick.
CC_SCOPES = (
    "cc.init",
    "cc.iter",  # the whole while loop; one iteration = one FastSV round
    "cc.gather",  # gf = f[f]
    "cc.spmv",  # the one sweep of a round, where gf changed: u = A (min) gf
    "cc.hook",  # stochastic hooking: the scatter-min of u into f[f]
    "cc.min",  # aggressive hooking, shortcutting, the fixed-point test
    "cc.jump",  # the pointer-jumping loop after the rounds
)


def fastsv(M, f0: DistVec | None = None):
    """FastSV on ``M``, an ``SpParMat`` or an ``EllParMat`` (the type
    picks the sweep, ``dist_spmv``'s seam): ``(labels, rounds, jumps)``,
    ``labels[v]`` the smallest vertex id of ``v``'s component, ``rounds``
    the iterations of the hooking loop and ``jumps`` those of the
    pointer-jumping loop after it.  ``f0`` (row-aligned int32, padding
    out of range) starts the loop from labels that name same-component
    vertices (``dynamic/refresh.py``'s warm start) instead of ``iota``.

    A round sweeps the matrix only where its grandparents ``f[f]`` are
    not the ones the last sweep read (``_fastsv``): FastSV usually ends
    with a round that only shortcuts and one that confirms, and the
    confirming round then costs its two vector subscripts and no sweep
    (four sweeps of five rounds on the Graph500 scale-20 graph).  The
    answer, ``rounds`` and ``jumps`` are what sweeping every round gives.
    A graph whose last changing round still hooks reuses nothing and
    pays one ``[n]`` compare a round; a deep one (a road network: tens
    of rounds) saves one sweep of many.

    Eager wrapper: the jitted programs return plain block arrays (the
    plain-outputs law) and, fourth, the rounds that swept; this rebuilds
    the DistVec outside and, with telemetry on, adds rounds and jumps to
    ``models.cc.rounds`` / ``.jumps`` and, for an ``EllParMat``, the
    sweeps to the ELL family (``_count_sweeps``)."""
    program = cc_fastsv_ell if isinstance(M, EllParMat) else cc_fastsv
    f0_blocks = None if f0 is None else f0.blocks
    blocks, rounds, jumps, sweeps = program(M, f0_blocks)
    if obs.ENABLED:
        # no warm-up of its own: the first traced call of a shape
        # publishes the program's op names (obs/opnames.py), AFTER the
        # call, so the call pays for the program as an untraced one does
        # and the publishing only for itself
        obs.opnames.publish_once(
            (program.__name__, M.grid, M.nrows, f0 is None,
             tuple(a.shape for a in jax.tree_util.tree_leaves(M))),
            lambda: program.lower(M, f0_blocks).compile().as_text(),
        )
        obs.count("models.cc.jobs")
        obs.count("models.cc.rounds", int(rounds))
        obs.count("models.cc.jumps", int(jumps))
        if isinstance(M, EllParMat):
            _count_sweeps(M, int(sweeps))
    labels = DistVec(blocks=blocks, length=M.nrows, align="row", grid=M.grid)
    return labels, rounds, jumps


def _count_sweeps(E: EllParMat, sweeps: int) -> None:
    """A job's ``sweeps`` into the ELL family, ``kind="cc"``, ``width=1``
    (``ellmat.count_sweep_work``, as a served batch's are): the one-lane
    sweep has no choice inside it, so the tally is made here, ``sweeps``
    dense sweeps of every class in every tile; the job is one of
    ``ell.batches``.  (An ``SpParMat``'s sweep has no classes and counts
    nothing.)"""
    slots = class_slots(E)
    tally = np.zeros(
        (E.grid.pr, E.grid.pc, len(slots), len(SWEEP_MODES)), np.int64)
    tally[..., 0] = sweeps
    count_sweep_work("cc", 1, tally, slots)
    obs.count("ell.batches", 1, kind="cc", width=1)


def connected_components(M) -> tuple[DistVec, jax.Array]:
    """``fastsv(M)``'s labels and round count."""
    return fastsv(M)[:2]


def _fastsv(M, f0_blocks):
    """Component labels (min vertex id in each component), the hooking
    loop's iteration count, the pointer-jumping loop's, and how many of
    the hooking rounds swept the matrix.

    M is interpreted structurally (any nonzero = edge) and must be
    symmetric; returns PLAIN row-aligned int32 label BLOCKS (the eager
    wrapper above rebuilds the DistVec); padding slots carry their own
    (out-of-range) ids and never interact with real vertices.

    A round's sweep reads ``gf = f[f]`` and a matrix that does not
    change, so the loop carries the ``gf`` its last sweep read and the
    ``u`` that sweep gave, and a round whose ``gf`` is that one takes
    the carried ``u`` (``lax.cond`` on a global ``any``: every tile of a
    mesh takes the same branch; the sweep, its realign and its gather
    table are all inside the branch).  Round 1 always sweeps.  Labels,
    rounds and jumps are bit for bit those of sweeping every round.
    Not done here: ``gf`` only ever falls, so a round that changes few
    entries of it could lower ``u`` by a push over those columns alone
    (upstream's ``SpMSpV``); that needs a column companion.
    """
    grid = M.grid
    n = M.nrows

    def mk(blocks):
        return DistVec(blocks=blocks, length=n, align="row", grid=grid)

    with jax.named_scope("cc.init"):
        if f0_blocks is None:
            f0_blocks = DistVec.iota(grid, n, jnp.int32, align="row").blocks

    def cond(state):
        _, changed, it, *_ = state
        return changed & (it < n)

    def step(state):
        fb, _, it, gf_swept, u_swept, sweeps = state
        f = mk(fb)
        with jax.named_scope("cc.gather"):
            gf = f.gather(f)  # grandparent labels f[f[i]]
        with jax.named_scope("cc.spmv"):
            # u[i] = min over neighbors j of gf[j]  (one semiring SpMV).
            # The sweep reads gf and nothing else that changes: where gf
            # is what the last sweep read, that sweep's u is this round's
            stale = (it == 0) | jnp.any(gf.blocks != gf_swept)
            ub = jax.lax.cond(
                stale,
                lambda: dist_spmv(
                    SELECT2ND_MIN, M, gf.realign("col")).blocks,
                lambda: u_swept,
            )
        with jax.named_scope("cc.hook"):
            # stochastic hooking: lower the parent's label
            f1 = f.scatter_combine(SELECT2ND_MIN, idx=f, src=mk(ub))
        with jax.named_scope("cc.min"):
            # aggressive hooking + shortcutting (elementwise minimums)
            nb = jnp.minimum(jnp.minimum(f1.blocks, ub), gf.blocks)
            changed = jnp.any(nb != fb)
        return (nb, changed, it + 1, gf.blocks, ub,
                sweeps + stale.astype(jnp.int32))

    with jax.named_scope("cc.iter"):
        # round 1 sweeps whatever the carried gf and u start as
        fb, _, rounds, _, _, sweeps = jax.lax.while_loop(
            cond, step,
            (f0_blocks, jnp.bool_(True), jnp.int32(0), f0_blocks,
             jnp.zeros_like(f0_blocks), jnp.int32(0)),
        )

    # Final pointer-jumping: compress remaining parent chains to roots.
    def jcond(state):
        _, changed, _ = state
        return changed

    def jstep(state):
        fb, _, it = state
        f = mk(fb)
        gf = f.gather(f)
        return gf.blocks, jnp.any(gf.blocks != fb), it + 1

    with jax.named_scope("cc.jump"):
        fb, _, jumps = jax.lax.while_loop(
            jcond, jstep, (fb, jnp.bool_(True), jnp.int32(0))
        )
    return fb, rounds, jumps, sweeps


@jax.jit
def cc_fastsv(A: SpParMat, f0_blocks=None):
    """``_fastsv`` over COO tiles (``parallel/spmv.py:dist_spmv``)."""
    return _fastsv(A, f0_blocks)


@jax.jit
def cc_fastsv_ell(E: EllParMat, f0_blocks=None):
    """``_fastsv`` over the ELL sweep (``ellmat.dist_spmv_ell``): the
    same loop under a program name of its own, so a trace's ``XLA
    Modules`` line and the compile cache tell the two apart."""
    return _fastsv(E, f0_blocks)


_STAR, _NONSTAR, _CONVERGED = np.int32(1), np.int32(0), np.int32(2)


def lacc(A: SpParMat) -> tuple[DistVec, jax.Array]:
    """Eager wrapper over ``_lacc_impl`` (plain-outputs law)."""
    blocks, niter = _lacc_impl(A)
    return (
        DistVec(blocks=blocks, length=A.nrows, align="row", grid=A.grid),
        niter,
    )


@jax.jit
def _lacc_impl(A: SpParMat):
    """LACC connected components (≈ Applications/CC.h:1035-1530,
    Azad-Buluç IPDPS'19): conditional star hooking, unconditional star
    hooking, shortcutting, and star detection, iterated until every vertex
    is converged. Returns (labels, iterations) like
    ``connected_components``.

    TPU-native reformulation: the reference's FullyDistSpVec
    Extract/Assign/EWiseApply choreography becomes dense masked gathers and
    scatter-mins on the [pa, L] parent/star blocks, and the whole loop is
    one ``lax.while_loop`` (no host round trips). Two deviations, both
    conservative-correct: (a) the reference's iteration-1 special cases
    (skipping the parent-star propagation, CC.h:1445-1462,1475-1485) are
    replaced by the uniform star-tracking path — marking extra vertices
    NONSTAR is always safe because StarCheck re-promotes them; (b) hook
    duplicate resolution is a deterministic scatter-min instead of the
    reference's unordered Assign.
    """
    grid = A.grid
    n = A.nrows
    NOHOOK = jnp.int32(2**31 - 1)  # SELECT2ND_MIN identity = "no neighbor"

    iota = DistVec.iota(grid, n, jnp.int32, align="row")

    def mk(blocks):
        return DistVec(blocks=blocks, length=n, align="row", grid=grid)

    # isolated vertices (degree 0) start converged (CC.h:1416-1417)
    from ..semiring import PLUS_TIMES
    from ..parallel.spmat import ones_i32

    deg = A.reduce(PLUS_TIMES, "cols", map_fn=ones_i32)
    star0 = jnp.where(deg.blocks == 0, _CONVERGED, _STAR)
    # padding slots: converged, pointing at themselves, never hook
    star0 = mk(star0).mask_padding(_CONVERGED).blocks

    def scatter_min(vec: DistVec, idx_blocks, src_blocks):
        return vec.scatter_combine(
            SELECT2ND_MIN, idx=mk(idx_blocks), src=mk(src_blocks)
        )

    def scatter_set(base_blocks, idx_blocks, src_blocks):
        """out[p] = (min over src hitting p) if any hit else base[p].

        The reference's Assign/Set hook application (overwrite, not
        monoid-combine) with deterministic min dup-resolution: a plain
        scatter-min into base would silently drop hooks whose value
        exceeds the target's current parent — livelocking unconditional
        hooking (the hooked star would stay a star forever)."""
        fresh = mk(jnp.full_like(base_blocks, NOHOOK))
        hit = scatter_min(fresh, idx_blocks, src_blocks).blocks
        return jnp.where(hit != NOHOOK, hit, base_blocks)

    def cond(state):
        _, star, it, done = state
        return (~done) & (it < n)

    def step(state):
        parent_b, star_b, it, _ = state
        parent = mk(parent_b)

        # --- conditional star hooking (CC.h:1195-1240) -----------------
        # mnp[u] = min over neighbors of parent[neighbor]
        mnp = dist_spmv(SELECT2ND_MIN, A, parent.realign("col"))
        hook = (star_b == _STAR) & (mnp.blocks < parent_b)
        # hook the star's root: parent[parent[u]] <- min mnp[u]
        tgt = jnp.where(hook, parent_b, -1)
        val = jnp.where(hook, mnp.blocks, NOHOOK)
        parent_b = scatter_min(mk(parent_b), tgt, val).blocks

        # star tracking after hooking (CC.h:1035-1064, uniform path):
        # hooks, their roots, and the hook targets all become NONSTAR.
        star_b = jnp.where(hook, _NONSTAR, star_b)
        star_b = scatter_min(mk(star_b), tgt, jnp.where(hook, _NONSTAR, NOHOOK)).blocks
        star_b = scatter_min(
            mk(star_b), val, jnp.where(hook, _NONSTAR, NOHOOK)
        ).blocks
        # stars read their parent's star flag (propagate non-starness)
        pstar = mk(star_b).gather(mk(parent_b))
        star_b = jnp.where(
            (star_b == _STAR) & (pstar.blocks == _NONSTAR), _NONSTAR, star_b
        )

        # --- unconditional star hooking (CC.h:1243-1320) ----------------
        # exclude star trees as targets: their parent-values become the
        # SELECT2ND_MIN identity, so only nonstar neighbors contribute.
        masked_parent = jnp.where(star_b == _STAR, NOHOOK, parent_b)
        mnp2 = dist_spmv(
            SELECT2ND_MIN, A, mk(masked_parent).realign("col")
        )
        hook2 = (star_b == _STAR) & (mnp2.blocks != NOHOOK)
        tgt2 = jnp.where(hook2, parent_b, -1)
        val2 = jnp.where(hook2, mnp2.blocks, NOHOOK)
        parent_b = scatter_set(parent_b, tgt2, val2)

        star_b = jnp.where(hook2, _NONSTAR, star_b)
        star_b = scatter_min(
            mk(star_b), tgt2, jnp.where(hook2, _NONSTAR, NOHOOK)
        ).blocks
        star_b = scatter_min(
            mk(star_b), val2, jnp.where(hook2, _NONSTAR, NOHOOK)
        ).blocks
        pstar = mk(star_b).gather(mk(parent_b))
        star_b = jnp.where(
            (star_b == _STAR) & (pstar.blocks == _NONSTAR), _NONSTAR, star_b
        )

        # remaining stars are converged (CC.h:1477)
        star_b = jnp.where(star_b == _STAR, _CONVERGED, star_b)
        done = jnp.all(star_b == _CONVERGED)

        # --- shortcut on nonstars (CC.h:1332-1345) ----------------------
        parent = mk(parent_b)
        gp = parent.gather(parent)
        parent_b = jnp.where(star_b == _NONSTAR, gp.blocks, parent_b)

        # --- star detection on nonstars (CC.h:1070-1124) ----------------
        active = star_b == _NONSTAR
        star_b = jnp.where(active, _STAR, star_b)
        parent = mk(parent_b)
        gp = parent.gather(parent)
        bad = active & (gp.blocks != parent_b)
        star_b = jnp.where(bad, _NONSTAR, star_b)
        # parents and grandparents of deep vertices are NONSTAR
        star_b = scatter_min(
            mk(star_b), jnp.where(bad, parent_b, -1),
            jnp.where(bad, _NONSTAR, NOHOOK),
        ).blocks
        star_b = scatter_min(
            mk(star_b), jnp.where(bad, gp.blocks, -1),
            jnp.where(bad, _NONSTAR, NOHOOK),
        ).blocks
        # leaves read their parent's flag
        pstar = mk(star_b).gather(mk(parent_b))
        star_b = jnp.where(
            active & (star_b == _STAR) & (pstar.blocks == _NONSTAR),
            _NONSTAR, star_b,
        )
        return parent_b, star_b, it + 1, done

    parent_b, _, niter, _ = jax.lax.while_loop(
        cond, step, (iota.blocks, star0, jnp.int32(0), jnp.bool_(False))
    )

    # compress remaining chains (stars may point one level up)
    def jcond(state):
        _, changed = state
        return changed

    def jstep(state):
        fb, _ = state
        gf = mk(fb).gather(mk(fb))
        return gf.blocks, jnp.any(gf.blocks != fb)

    parent_b, _ = jax.lax.while_loop(
        jcond, jstep, (parent_b, jnp.bool_(True))
    )
    return parent_b, niter


def num_components(labels: DistVec) -> int:
    """Host helper: count distinct labels among real (non-padding) slots."""
    import numpy as np

    return int(np.unique(labels.to_global()).size)
