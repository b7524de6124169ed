"""Warm-restart recompute: repair analytics instead of re-deriving them.

The algebra allows incremental recompute for the kinds the engine
serves as whole-graph analytics:

* **BFS levels** — after an INSERT-ONLY delta, old levels are valid
  upper bounds, so a min-plus relaxation seeded from them converges to
  the exact new levels in ~(changed-region diameter) sweeps instead of
  a full traversal ("delta-frontier repair": the first sweep relaxes
  exactly the endpoints of changed edges, later sweeps re-expand only
  from rows the previous sweep improved).  Deletions can RAISE levels,
  which no monotone repair can express — those fall back to a cold run.
* **Connected components** — same monotonicity: insertions only merge
  components, so FastSV seeded from the previous labels (each vertex
  already pointing at its old component's minimum) re-converges in a
  few hook/shortcut rounds.  Deletions may split — cold fallback.
* **PageRank** — the power iteration converges from ANY starting
  vector, so every delta warm-restarts from the previous ranks; small
  perturbations sit near the fixed point and save most iterations.

All three run over the engine's loaded ``EllParMat`` artifacts (the
same operands the serve plans use) as single jitted programs, and are
exposed through ``GraphEngine.refresh(kind)`` — which owns the cached
previous results, version lineage checks (``GraphVersion.delta_from``),
and the cold-vs-warm decision.  Obs: ``dynamic.refresh.*``.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..semiring import MIN_PLUS, PLUS_TIMES

#: Kinds ``GraphEngine.refresh`` understands.
REFRESH_KINDS = ("bfs", "cc", "pagerank")

#: Sentinel for unreached vertices in refresh("bfs") level vectors.
UNREACHED = np.int32(-1)


# -- BFS level repair --------------------------------------------------------


@jax.jit
def _bfs_relax_impl(E, lev_blocks):
    """Min-plus relaxation to fixpoint: ``lev <- min(lev, min over
    in-neighbors j of lev[j] + 1)``.  From a cold start (inf everywhere
    except the root) this IS BFS; from a warm start (old levels after
    insert-only deltas) it repairs.  Returns (blocks, sweeps)."""
    from ..parallel.ellmat import dist_spmv_ell
    from ..parallel.vec import DistVec

    grid, n = E.grid, E.nrows

    def mk(blocks):
        return DistVec(blocks=blocks, length=n, align="row", grid=grid)

    def cond(state):
        _, changed, it = state
        return changed & (it < n)

    def step(state):
        xb, _, it = state
        y = dist_spmv_ell(MIN_PLUS, E, mk(xb).realign("col"))
        nb = jnp.minimum(xb, y.blocks)
        return nb, jnp.any(nb != xb), it + 1

    blocks, _, niter = jax.lax.while_loop(
        cond, step, (lev_blocks, jnp.bool_(True), jnp.int32(0))
    )
    return blocks, niter


def _bfs_refresh(engine, root: int, prev: np.ndarray | None):
    from ..parallel.vec import DistVec

    n = engine.nrows
    if prev is None:
        lev = np.full(n, np.inf, np.float32)
        lev[int(root)] = 0.0
    else:
        lev = np.where(prev < 0, np.inf, prev).astype(np.float32)
    x0 = DistVec.from_global(
        engine.grid, lev, align="row", fill=np.float32(np.inf)
    )
    blocks, niter = _bfs_relax_impl(engine.E, x0.blocks)
    out = DistVec(
        blocks=blocks, length=n, align="row", grid=engine.grid
    ).to_global()
    levels = np.where(np.isfinite(out), out, -1).astype(np.int32)
    return levels, int(niter)


# -- connected-components repair ---------------------------------------------


def _cc_refresh(engine, prev: np.ndarray | None):
    """FastSV over the loaded ``EllParMat`` (``models/cc.py:fastsv``),
    cold from ``iota`` or warm from the previous labels: any start whose
    entries name SAME-COMPONENT vertices converges to the per-component
    minimum, and previous labels qualify after insert-only deltas."""
    from ..models.cc import fastsv
    from ..parallel.vec import DistVec

    f0 = None
    if prev is not None:
        f0 = DistVec.from_global(
            engine.grid, np.asarray(prev, np.int32), align="row"
        )
        # padding slots must carry self-ids out of range, like iota does
        f0 = f0.mask_padding(np.int32(2**31 - 1))
    labels, rounds, _ = fastsv(engine.E, f0)
    return labels.to_global().astype(np.int32), int(rounds)


# -- PageRank restart --------------------------------------------------------


@partial(jax.jit, static_argnames=("alpha", "tol", "max_iters"))
def _pagerank_ell_impl(P_ell, dangling_col, x0_blocks,
                       alpha: float = 0.85, tol: float = 1e-6,
                       max_iters: int = 100):
    """Whole-graph PageRank over the loaded transition matrix with an
    explicit starting vector (``models/pagerank.py:_pagerank_impl``'s
    loop, retargeted at the serving artifacts ``P_ell``/``dangling``).
    A warm ``x0`` near the fixed point saves most iterations."""
    from ..parallel.ellmat import dist_spmv_ell
    from ..parallel.vec import DistVec

    grid, n = P_ell.grid, P_ell.nrows
    col_gids = DistVec.iota(grid, n, jnp.int32, align="col").blocks
    dang_mask = jnp.where(col_gids < n, dangling_col, 0.0)
    row_valid = DistVec.iota(grid, n, jnp.int32, align="row").blocks < n

    def mk(blocks):
        return DistVec(blocks=blocks, length=n, align="row", grid=grid)

    def cond(state):
        _, err, it = state
        return (err > tol) & (it < max_iters)

    def step(state):
        xb, _, it = state
        x_col = mk(xb).realign("col")
        spread = dist_spmv_ell(PLUS_TIMES, P_ell, x_col)
        dmass = jnp.sum(dang_mask * x_col.blocks)
        base = (1.0 - alpha) / n + alpha * dmass / n
        nb = jnp.where(row_valid, alpha * spread.blocks + base, 0.0)
        err = jnp.sum(jnp.abs(nb - xb))
        return nb, err, it + 1

    xb, _, niter = jax.lax.while_loop(
        cond, step, (x0_blocks, jnp.float32(jnp.inf), jnp.int32(0))
    )
    return xb, niter


def _pagerank_refresh(engine, prev: np.ndarray | None):
    from ..parallel.vec import DistVec

    n = engine.nrows
    if engine.P_ell is None:
        raise ValueError(
            "refresh('pagerank') needs the pagerank artifacts "
            "(engine kinds= did not include 'pagerank')"
        )
    x0 = (
        np.full(n, 1.0 / n, np.float32) if prev is None
        else np.asarray(prev, np.float32)
    )
    v0 = DistVec.from_global(engine.grid, x0, align="row")
    alpha, tol, iters = engine.pagerank_opts
    blocks, niter = _pagerank_ell_impl(
        engine.P_ell, engine.dangling.realign("col").blocks, v0.blocks,
        alpha=alpha, tol=tol, max_iters=iters,
    )
    ranks = DistVec(
        blocks=blocks, length=n, align="row", grid=engine.grid
    ).to_global().astype(np.float32)
    return ranks, int(niter)


# -- the engine-facing entry -------------------------------------------------


def refresh_analytic(engine, kind: str, root: int | None = None,
                     force_cold: bool = False) -> dict:
    """Compute (or repair) one whole-graph analytic for the engine's
    CURRENT version.  The engine's ``_analytics`` cache holds the
    previous result + the version it was computed on; the warm path is
    taken when the current version's ``delta_from`` lineage points at
    exactly the cached version AND the delta is repair-compatible
    (insert-only for bfs/cc; anything for pagerank).  Called under the
    engine's execution lock by ``GraphEngine.refresh``."""
    if kind not in REFRESH_KINDS:
        raise ValueError(
            f"unknown refresh kind {kind!r}; expected {REFRESH_KINDS}"
        )
    if kind == "bfs":
        if root is None:
            raise ValueError("refresh('bfs') needs root=")
        root = int(root)
        if not (0 <= root < engine.nrows):
            raise ValueError(f"root {root} outside [0, {engine.nrows})")
    ck = (kind, root if kind == "bfs" else None)
    entry = engine._analytics.get(ck)
    vid = engine.version_id
    if entry is not None and obs.ENABLED:
        # the ROADMAP-named freshness gauge: how many graph versions
        # the cached analytic lags the served version at refresh time
        # (0 = the cache answers for the current graph)
        obs.gauge(
            "dynamic.freshness.versions_behind",
            vid - entry["vid"], kind=kind,
        )
    if entry is not None and entry["vid"] == vid and not force_cold:
        engine._refresh_modes["cached"] = (
            engine._refresh_modes.get("cached", 0) + 1
        )
        obs.count("dynamic.refresh.runs", kind=kind, mode="cached")
        return {**entry, "mode": "cached", "latency_s": 0.0}

    prev = None
    mode = "cold"
    reason = "first" if entry is None else "lineage"
    if entry is not None and not force_cold:
        delta = getattr(engine.version, "delta_from", None)
        if delta is not None and delta[0] == entry["vid"]:
            _parent, ins, rem = delta
            if kind == "pagerank":
                prev, mode, reason = entry["result"], "warm", ""
            elif len(rem) == 0:  # monotone repair needs insert-only
                prev, mode, reason = entry["result"], "warm", ""
            else:
                reason = "deletes"
    elif force_cold:
        reason = "forced"

    t0 = time.perf_counter()
    if kind == "bfs":
        result, niter = _bfs_refresh(engine, root, prev)
    elif kind == "cc":
        result, niter = _cc_refresh(engine, prev)
    else:
        result, niter = _pagerank_refresh(engine, prev)
    dt = time.perf_counter() - t0
    out = {"kind": kind, "vid": vid, "result": result, "niter": niter}
    engine._analytics[ck] = out
    engine._refresh_modes[mode] = engine._refresh_modes.get(mode, 0) + 1
    obs.count("dynamic.refresh.runs", kind=kind, mode=mode)
    obs.observe("dynamic.refresh.iters", niter, kind=kind, mode=mode)
    obs.observe("dynamic.refresh.latency_s", dt, kind=kind, mode=mode)
    if obs.ENABLED:
        # repair-vs-cold ratio over this engine's recompute history —
        # the streaming lane's warm-start payoff as one gauge
        warm = engine._refresh_modes.get("warm", 0)
        cold = engine._refresh_modes.get("cold", 0)
        if warm + cold:
            obs.gauge(
                "dynamic.freshness.repair_ratio", warm / (warm + cold)
            )
    return {
        **out, "mode": mode, "cold_reason": reason, "latency_s": dt,
    }
