"""PageRank — power iteration with teleport (≈ Applications/PageRank.cpp).

The reference computes out-degrees with ``A.Reduce(Column)``
(``PageRank.cpp:97``), normalizes columns with ``DimApply``, and runs the
``SpMV<PlusTimes>`` power loop (``:126-157``).  Same schedule here, with the
dangling-mass correction folded in (columns with zero out-degree teleport
uniformly), and the whole loop compiled as one ``lax.while_loop`` with an
L1-convergence test.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import PAD_ROOT
from ..semiring import PLUS_TIMES
from ..parallel.spmat import SpParMat, ones_f32
from ..parallel.spmv import dist_spmv
from ..parallel.vec import DistVec


def _scale(a, s):
    return a * s


def pagerank(A, alpha=0.85, tol=1e-6, max_iters=100):
    """Eager wrapper over ``_pagerank_impl`` (plain-outputs law)."""
    blocks, niter = _pagerank_impl(
        A, alpha=alpha, tol=tol, max_iters=max_iters
    )
    return (
        DistVec(blocks=blocks, length=A.nrows, align="row", grid=A.grid),
        niter,
    )


@partial(jax.jit, static_argnames=("alpha", "tol", "max_iters"))
def _pagerank_impl(
    A: SpParMat,
    alpha: float = 0.85,
    tol: float = 1e-6,
    max_iters: int = 100,
):
    """Ranks over the column-stochastic normalization of A.

    A[i, j] != 0 means edge j -> i (j links to i). Returns PLAIN
    (row-aligned float32 rank blocks summing to 1, iterations) — the
    eager wrapper above rebuilds the DistVec (plain-outputs law).
    """
    grid = A.grid
    n = A.nrows
    # Out-degree of j = # entries in column j (structural).
    outdeg = A.reduce(PLUS_TIMES, axis="rows", map_fn=ones_f32)
    inv_deg = outdeg.apply(
        lambda d: jnp.where(d > 0, 1.0 / jnp.maximum(d, 1.0), 0.0)
    )
    # Column-stochastic scale: P[i,j] = A[i,j] / outdeg[j] (structure-wise).
    P = A.apply(ones_f32).dim_apply(inv_deg, _scale, axis="cols")
    dangling = outdeg.apply(lambda d: (d == 0).astype(jnp.float32))
    # Mask padding columns out of the dangling-mass sum.
    col_gids = DistVec.iota(grid, n, jnp.int32, align="col").blocks
    dang_mask = jnp.where(col_gids < n, dangling.blocks, 0.0)

    x0 = jnp.where(
        DistVec.iota(grid, n, jnp.int32, align="row").blocks < n, 1.0 / n, 0.0
    )

    def mk_row(blocks):
        return DistVec(blocks=blocks, length=n, align="row", grid=grid)

    def cond(state):
        _, err, it = state
        return (err > tol) & (it < max_iters)

    def step(state):
        xb, _, it = state
        x_col = mk_row(xb).realign("col")
        spread = dist_spmv(PLUS_TIMES, P, x_col)
        dmass = jnp.sum(dang_mask * x_col.blocks)
        base = (1.0 - alpha) / n + alpha * dmass / n
        nb = alpha * spread.blocks + base
        nb = jnp.where(
            DistVec.iota(grid, n, jnp.int32, align="row").blocks < n, nb, 0.0
        )
        err = jnp.sum(jnp.abs(nb - xb))
        return nb, err, it + 1

    xb, _, niter = jax.lax.while_loop(
        cond, step, (x0, jnp.float32(jnp.inf), jnp.int32(0))
    )
    return xb, niter


def pagerank_batch(P_ell, sources, dangling, alpha=0.85, tol=1e-6,
                   max_iters=100):
    """Eager wrapper over ``_pagerank_batch_impl`` (plain-outputs law)."""
    from ..parallel.vec import DistMultiVec

    blocks, niter = _pagerank_batch_impl(
        P_ell, sources, dangling, alpha=alpha, tol=tol,
        max_iters=max_iters,
    )
    return (
        DistMultiVec(
            blocks=blocks, length=P_ell.nrows, align="row",
            grid=P_ell.grid,
        ),
        niter,
    )


@partial(jax.jit, static_argnames=("alpha", "tol", "max_iters"))
def _pagerank_batch_impl(
    P_ell,
    sources: jax.Array,
    dangling: "DistVec",
    alpha: float = 0.85,
    tol: float = 1e-6,
    max_iters: int = 100,
):
    """Personalized PageRank for W sources in ONE program (the multi-root
    amortization of the batched BFS applied to PageRank: the measured chip
    gather is per-INDEX bound with payload lanes nearly free, so W rank
    chains cost ~one — round-2 notes; PERF.md §5 has today's lane costs).

    ``P_ell``: the COLUMN-NORMALIZED transition matrix as an EllParMat
    (entry (i,j) = 1/outdeg(j) for edge j->i — normalize host-side while
    building the ELL buckets). ``sources``: [W] int32 personalization
    vertices; slots holding ``models.PAD_ROOT`` are inert padding lanes
    (all-zero ranks — the serve batcher's lane padding). Returns
    (row-aligned DistMultiVec of ranks [n, W] — each live lane sums to
    1, teleporting to ITS source — and the iteration count).

    Reference: ``PageRank.cpp:126-157``'s loop, batched; personalization
    follows the standard PPR formulation (teleport to e_s instead of 1/n).
    """
    from ..parallel.ellmat import dist_spmv_ell_multi
    from ..parallel.vec import DistMultiVec

    grid = P_ell.grid
    n = P_ell.nrows
    W = sources.shape[0]

    row_gids = DistVec.iota(grid, n, jnp.int32, align="row").blocks  # [pr, lr]
    # PAD_ROOT lanes get an all-zero teleport vector: they carry no mass
    # and converge immediately (the iota gid table never holds PAD_ROOT,
    # but the explicit guard keeps the contract independent of that)
    live = (sources[None, None, :] != PAD_ROOT)
    e_s = (
        (row_gids[..., None] == sources[None, None, :]) & live
    ).astype(jnp.float32)
    dang_row = dangling.realign("row").blocks  # [pr, lr]
    rowvalid = (row_gids < n)[..., None]

    def mk(blocks):
        return DistMultiVec(blocks=blocks, length=n, align="row", grid=grid)

    def cond(state):
        _, err, it = state
        return (err > tol) & (it < max_iters)

    def step(state):
        xb, _, it = state
        spread = dist_spmv_ell_multi(PLUS_TIMES, P_ell, mk(xb))
        # per-lane dangling mass teleports to that lane's source
        dmass = jnp.sum(dang_row[..., None] * xb, axis=(0, 1))  # [W]
        nb = alpha * (spread.blocks + dmass[None, None, :] * e_s) + (
            1.0 - alpha
        ) * e_s
        nb = jnp.where(rowvalid, nb, 0.0)
        err = jnp.max(jnp.sum(jnp.abs(nb - xb), axis=(0, 1)))
        return nb, err, it + 1

    xb, _, niter = jax.lax.while_loop(
        cond, step, (e_s, jnp.float32(jnp.inf), jnp.int32(0))
    )
    return xb, niter
