"""Micro-probe pass: MEASURE the admissible SpGEMM rungs on a bounded
downsampled proxy and write the winner into the plan store.

On a plan-store miss (tuner probing enabled, no arg/env override) the
router calls ``probe_spgemm``:

1. **Deterministic, degree-preserving downsample** — the operands'
   host COO maps through a seeded permutation into a pow2 proxy
   rectangle (``COMBBLAS_TUNER_PROBE_MAX_DIM``, default 2048), with
   one axis RESTRICTED and the other FOLDED per operand so the proxy
   keeps the density band the plan key records (see
   ``downsample_coo``).  The same inputs + seed always yield the same
   proxy, so two replicas probing the same miss converge on the same
   plan.
2. **Admissibility at REAL scale** — candidate rungs are gated on the
   REAL shapes (a tier admissible at proxy scale may be structurally
   impossible at production scale, e.g. the mxu envelope), using the
   same predicates as ``choose_spgemm_tier``.
3. **Bounded measurement** — each candidate compiles once (untimed)
   then one timed run; the cumulative timed seconds are capped by
   ``COMBBLAS_TUNER_PROBE_BUDGET_S`` (default 30 s) with the
   heuristic's own choice always measured FIRST, so budget exhaustion
   still yields a measured plan.  Probe cost is obs-visible
   (``tuner.probe.{runs,seconds,winner}``) and recorded in the store's
   host counters either way.

The proxy runs on the SAME grid as the real product (stage collectives
and per-device tile shapes are part of what distinguishes the rungs).
Probing currently covers the 2D ladder; products routed with a
``grid3`` fall back to the heuristic's windowed3d upgrade rule.
"""

from __future__ import annotations

import time

import numpy as np

from .. import obs
from . import config
from .store import PlanRecord, PlanStore, PlanKey


def downsample_coo(
    rows,
    cols,
    dims: tuple[int, int],
    proxy_dims: tuple[int, int],
    seed: int = 0,
    modes: tuple[str, str] = ("restrict", "fold"),
):
    """Deterministically downsample a host COO to a proxy rectangle,
    PRESERVING the density band the plan key records.

    Each axis is mapped through a seeded permutation of its length and
    then either ``"restrict"``-ed (keep ids < proxy dim — drops a
    1/ratio fraction of entries) or ``"fold"``-ed (id mod proxy dim —
    keeps every entry).  Restricting ONE axis and folding the other
    keeps the per-row average degree of the original (restricting both
    would shrink degree by the sampling ratio and measure the rungs at
    the wrong density band — the scan/windowed ranking flips with
    density, r7 data).  The probe uses ``("restrict", "fold")`` for A
    and ``("fold", "restrict")`` for B, so the shared k axis carries
    the SAME permutation+fold on both operands (same (length, seed)
    pair → same permutation) and A·B stays structurally consistent.
    Pure function of (inputs, seed): the determinism contract."""
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    keep = np.ones(len(rows), bool)
    out = []
    for x, dim, pdim, mode in (
        (rows, dims[0], proxy_dims[0], modes[0]),
        (cols, dims[1], proxy_dims[1], modes[1]),
    ):
        mapped = _axis_perm(dim, seed)[x]
        if mode == "restrict":
            keep &= mapped < pdim
        else:
            assert mode == "fold", mode
            mapped = mapped % pdim
        out.append(mapped)
    return (
        out[0][keep].astype(np.int64),
        out[1][keep].astype(np.int64),
        keep,
    )


def _dedup_sum(r, c, v, ncols: int):
    """Host sum-combine of duplicate (row, col) proxy entries."""
    key = r.astype(np.int64) * np.int64(ncols) + c
    uniq, inv = np.unique(key, return_inverse=True)
    vv = np.zeros(len(uniq), np.asarray(v).dtype)
    np.add.at(vv, inv, np.asarray(v))
    return (
        (uniq // ncols).astype(np.int64),
        (uniq % ncols).astype(np.int64),
        vv,
    )


def _axis_perm(length: int, seed: int) -> np.ndarray:
    """One seeded permutation per (axis length, seed): shared axes (the
    k dimension of A·B, or all three axes of A²) map identically."""
    rng = np.random.default_rng(np.uint64(seed * 1_000_003 + length))
    return rng.permutation(int(length))


def _proxy_dim(dim: int, max_dim: int) -> int:
    """Pow2 proxy dimension: probe compiles land in a handful of fixed
    shapes shared across keys.  Never exceeds ``max_dim`` — when the
    pow2 ceiling would overshoot a non-pow2 cap, round DOWN instead
    (the operator's probe budget is a bound, not a suggestion)."""
    d = min(int(dim), int(max_dim))
    p = 1 << max(d - 1, 1).bit_length()
    if p > max_dim:
        p >>= 1
    return max(p, 2)


def admissible_tiers(sr, A, B, backend: str) -> list[str]:
    """Candidate rungs for the probe, gated at REAL scale with the
    router's own predicates; the heuristic's choice is listed FIRST
    (it is measured even when the budget runs out after one rung)."""
    from ..ops.spgemm import scatter_combine_for
    from ..parallel import spgemm as sp

    cands = []
    max_dim = max(A.local_rows, A.local_cols, B.local_cols)
    cells = A.local_rows * B.local_cols
    if (
        max_dim <= sp.MXU_MAX_TILE_DIM
        and sr.name in sp._PALLAS_KINDS
        and not (
            sp.coo_has_duplicates(A)
            or (B is not A and sp.coo_has_duplicates(B))
        )
    ):
        cands.append("mxu")
    if (
        scatter_combine_for(sr) is not None
        and cells <= sp.WINDOWED_MAX_TILE_CELLS
        and (
            backend == "scatter"
            or (
                sr.name in sp._PALLAS_KINDS
                and sp.dot_panel_feasible(B.local_rows, B.local_cols)
            )
        )
    ):
        cands.append("windowed")
    cands.append("scan")
    heur = sp._choose_spgemm_tier_2d(
        sr, A, B, backend=backend, assume_unique=True
    )
    if heur in cands:
        cands.remove(heur)
        cands.insert(0, heur)
    return cands


def _default_measure(fn) -> float:
    """Wall-time one warm run (the closure compiles untimed before)."""
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out.vals)
    return time.perf_counter() - t0


def probe_spgemm(
    sr,
    A,
    B,
    *,
    backend: str,
    store: PlanStore | None = None,
    key: PlanKey | None = None,
    budget_s: float | None = None,
    max_dim: int | None = None,
    seed: int = 0,
    measure=None,
    tier_order=None,
    geometry: bool = True,
) -> PlanRecord | None:
    """Measure the admissible rungs on the downsampled proxy; return
    the winning :class:`PlanRecord` (and persist it into ``store``
    under ``key`` when both are given), or ``None`` when no
    measurement was possible (empty proxy) — the caller then falls
    back to the heuristic.

    ``measure`` injects the cost functional (tests use a
    deterministic fake; default wall time);
    ``tier_order`` overrides the admissibility-gated candidate list
    and ``geometry=False`` skips the windowed block-shape sweep (both
    for deterministic tests — production callers leave the defaults)."""
    from ..parallel.spmat import SpParMat

    budget_s = config.probe_budget_s() if budget_s is None else budget_s
    max_dim = config.probe_max_dim() if max_dim is None else max_dim
    measure = _default_measure if measure is None else measure

    ra, ca, va = A.to_global_coo()
    pm = _proxy_dim(A.nrows, max_dim)
    pk = _proxy_dim(A.ncols, max_dim)
    pn = _proxy_dim(B.ncols, max_dim)
    # degree-preserving split: A restricts rows / folds cols, B folds
    # rows / restricts cols — both operands keep the density band their
    # plan key records, and the shared k axis folds identically
    par, pac, keep_a = downsample_coo(
        ra, ca, (A.nrows, A.ncols), (pm, pk), seed=seed,
        modes=("restrict", "fold"),
    )
    rb, cb, vb = (ra, ca, va) if B is A else B.to_global_coo()
    pbr, pbc, keep_b = downsample_coo(
        rb, cb, (B.nrows, B.ncols), (pk, pn), seed=seed,
        modes=("fold", "restrict"),
    )
    if len(par) == 0 or len(pbr) == 0:
        return None  # degenerate proxy: nothing to measure
    grid = A.grid
    # folding can alias two source entries onto one proxy cell — dedup
    # (sum-combine) so the mxu candidate's unique-entries precondition
    # holds on the proxy exactly as on a compacted production input
    pA = SpParMat.from_global_coo(
        grid, *_dedup_sum(par, pac, np.asarray(va)[keep_a], pk), pm, pk
    )
    pB = SpParMat.from_global_coo(
        grid, *_dedup_sum(pbr, pbc, np.asarray(vb)[keep_b], pn), pk, pn
    )

    from ..parallel.spgemm import spgemm_auto

    cands = (
        list(tier_order) if tier_order is not None
        else admissible_tiers(sr, A, B, backend)
    )
    costs: dict[str, float] = {}
    spent = 0.0
    runs = 0
    with obs.span("tuner.probe", sr=sr.name, dim=pm):
        for tier in cands:
            if costs and spent >= budget_s:
                if obs.ENABLED:
                    obs.count("tuner.probe.budget_exhausted")
                break

            def run(tier=tier):
                return spgemm_auto(
                    sr, pA, pB, tier=tier, backend=backend,
                    assume_unique=(tier != "mxu"),
                )

            try:
                run()  # compile + warm (untimed)
                dt = float(measure(run))
            except Exception:
                # a rung that faults on the proxy is simply not a
                # candidate (never let probing take the caller down)
                if obs.ENABLED:
                    obs.count("tuner.probe.errors", tier=tier)
                continue
            costs[tier] = dt
            spent += dt
            runs += 1
            if obs.ENABLED:
                obs.count("tuner.probe.runs", tier=tier)
    if store is not None:
        store.record_probe(runs, spent)
    if obs.ENABLED:
        obs.count("tuner.probe.seconds", spent)
    if not costs:
        return None
    winner = min(costs, key=costs.get)
    if obs.ENABLED:
        obs.count("tuner.probe.winner", tier=winner)
    # -- window-geometry sweep (round 12, ROADMAP follow-up): the tier
    # probe measured the WINDOWED rung at its default block geometry;
    # when windowed won and budget remains, sweep a small block_rows /
    # block_cols grid on the same proxy and persist the winning
    # geometry WITH the plan.  Proxy-scale geometry transfers as a
    # measured hint — a real-scale plan put() under the same key
    # overwrites it.
    best_geo = (None, None)
    if geometry and winner == "windowed" and spent < budget_s:
        best_cost = costs[winner]
        geo_runs, geo_spent = 0, 0.0
        geo_cands = _geometry_candidates(pm, pn)
        with obs.span("tuner.probe.geometry", dim=pm):
            for br, bc in geo_cands:
                if spent + geo_spent >= budget_s:
                    if obs.ENABLED:
                        obs.count("tuner.probe.budget_exhausted")
                    break

                def run_geo(br=br, bc=bc):
                    return spgemm_auto(
                        sr, pA, pB, tier="windowed", backend=backend,
                        block_rows=br, block_cols=bc,
                        assume_unique=True,
                    )

                try:
                    run_geo()  # compile + warm (untimed)
                    dt = float(measure(run_geo))
                except Exception:
                    if obs.ENABLED:
                        obs.count("tuner.probe.errors", tier="windowed")
                    continue
                geo_spent += dt
                geo_runs += 1
                if obs.ENABLED:
                    obs.count("tuner.probe.geometry_runs")
                if dt < best_cost:
                    best_cost, best_geo = dt, (br, bc)
        if store is not None:
            store.record_probe(geo_runs, geo_spent)
        if obs.ENABLED and geo_spent:
            obs.count("tuner.probe.seconds", geo_spent)
        costs[winner] = best_cost
        if best_geo != (None, None):
            # the candidates are FRACTIONS of the proxy dims; persist
            # them rescaled to the REAL dims the plan key describes —
            # replaying a proxy-absolute block size at production
            # scale would mint thousands of tiny windows (when the
            # proxy wasn't downsampled the factor is 1: the exact
            # measured geometry ships)
            sm = -(-int(A.nrows) // pm)
            sn = -(-int(B.ncols) // pn)
            br, bc = best_geo
            best_geo = (
                None if br is None else int(br) * sm,
                None if bc is None else int(bc) * sn,
            )
    rec = PlanRecord(
        tier=winner, cost_s=costs[winner], source="probe",
        probe_dim=pm,
        block_rows=best_geo[0], block_cols=best_geo[1],
    )
    if store is not None and key is not None:
        store.put(key, rec)
    return rec


def _geometry_candidates(pm: int, pn: int) -> list[tuple]:
    """Bounded non-default block-geometry grid for the windowed sweep:
    a handful of pow2 fractions of the proxy dims (the kernel default
    was already measured by the tier pass), deduped and capped at FOUR
    so the sweep stays a small multiple of one tier measurement —
    every candidate is one real compile on the proxy."""
    brs = sorted({max(pm // 8, 16), max(pm // 2, 32)})
    bcs = [None, max(pn // 4, 16)]
    cands = [(br, bc) for br in brs for bc in bcs]
    seen, out = set(), []
    for g in cands:
        if g not in seen and g != (None, None):
            seen.add(g)
            out.append(g)
    return out[:4]


def probe_spgemm3d(
    sr,
    A3,
    B3,
    *,
    store: PlanStore | None = None,
    key: PlanKey | None = None,
    budget_s: float | None = None,
    measure=None,
    candidates=None,
) -> PlanRecord | None:
    """Measure admissible (tier, merge) pairs of the 3D entry ON THE
    REAL OPERANDS and return / persist the winner — the op="spgemm3d"
    micro-probe (round 13; before it the 3D entry had no probe pass
    and store records could only be bench-seeded).

    Like ``probe_spmm`` there is no downsampled proxy: a 3D probe run
    is a warm run of a kernel the caller was about to run anyway, the
    candidate list is small (≤ 5), and the pass is opt-in
    (``COMBBLAS_TUNER_PROBE=1``) and budget-bounded with the
    heuristic's own choice (esc + its default merge) measured FIRST,
    so exhaustion still yields a measured plan.  The sweep covers the
    merge knob — the fiber reduce's combine tier is exactly what the
    CPU-mesh schedule measurement can rank (sort work is local) —
    and persists the winner's ``merge`` in the plan record."""
    import jax

    from ..ops.spgemm import scatter_combine_for
    from ..parallel import mesh3d

    budget_s = config.probe_budget_s() if budget_s is None else budget_s

    if candidates is None:
        # heuristic first (esc with its own merge resolution), then the
        # merge alternates, then the windowed tier with ITS heuristic
        # merge + the sort control — ≤ 5 real-scale runs, each one a
        # kernel the caller could legitimately route to
        candidates = [("esc", None), ("esc", "runs")]
        if scatter_combine_for(sr) is not None:
            candidates += [
                ("windowed", None), ("windowed", "sort"),
            ]
            if A3.grid.layers >= 2:
                candidates.append(("windowed", "hash"))
        # a fleet-wide COMBBLAS_SPGEMM_MERGE makes the None-merge
        # candidates resolve to the env value — dedupe so the budget
        # never times the IDENTICAL kernel twice (and noise never
        # picks between two equal entries)
        env_merge = config.env_merge()
        if env_merge is not None:
            seen, uniq = set(), []
            for tier, mg in candidates:
                eff = (tier, mg if mg is not None else env_merge)
                if eff not in seen:
                    seen.add(eff)
                    uniq.append((tier, mg))
            candidates = uniq

    def _measure_default(fn) -> float:
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out.vals)
        return time.perf_counter() - t0

    measure = _measure_default if measure is None else measure
    costs: dict[tuple, float] = {}
    spent = 0.0
    runs = 0
    with obs.span(
        "tuner.probe", sr=sr.name, dim=int(A3.nrows), op="spgemm3d"
    ):
        for tier, merge in candidates:
            if costs and spent >= budget_s:
                if obs.ENABLED:
                    obs.count("tuner.probe.budget_exhausted")
                break

            def run(tier=tier, merge=merge):
                return mesh3d.spgemm3d(sr, A3, B3, tier=tier,
                                       merge=merge)

            try:
                run()  # compile + warm (untimed)
                dt = float(measure(run))
            except Exception:
                if obs.ENABLED:
                    obs.count("tuner.probe.errors", tier=tier)
                continue
            costs[(tier, merge)] = dt
            spent += dt
            runs += 1
            if obs.ENABLED:
                obs.count("tuner.probe.runs", tier=tier)
    if store is not None:
        store.record_probe(runs, spent)
    if obs.ENABLED:
        obs.count("tuner.probe.seconds", spent)
    if not costs:
        return None
    winner = min(costs, key=costs.get)
    if obs.ENABLED:
        obs.count("tuner.probe.winner", tier=winner[0])
    rec = PlanRecord(
        tier=winner[0], merge=winner[1], cost_s=costs[winner],
        source="probe", probe_dim=int(A3.nrows),
    )
    if store is not None and key is not None:
        store.put(key, rec)
    return rec


def probe_spmm(
    sr,
    E,
    X,
    *,
    store: PlanStore | None = None,
    key: PlanKey | None = None,
    budget_s: float | None = None,
    measure=None,
) -> PlanRecord | None:
    """Measure the admissible SpMM backends ON THE REAL OPERANDS and
    return / persist the winner (the op="spmm" micro-probe).

    Unlike the SpGEMM probe there is no downsampled proxy: an SpMM
    probe is at most two warm runs of a kernel the caller was about to
    run anyway (the candidate set is {mxu_gather, scatter} for
    plus_times, a single backend otherwise — in which case there is
    nothing to measure and ``None`` is returned).  The heuristic's
    choice is measured FIRST so budget exhaustion still yields a
    measured plan; cost is obs-visible under the same
    ``tuner.probe.*`` counters as the SpGEMM pass."""
    from ..parallel import spmm as spmm_mod

    cands = list(spmm_mod.admissible_spmm_backends(sr))
    if len(cands) < 2:
        return None
    heur = spmm_mod.spmm_backend_heuristic(sr)
    if heur in cands:
        cands.remove(heur)
        cands.insert(0, heur)
    budget_s = config.probe_budget_s() if budget_s is None else budget_s

    def _measure_default(fn) -> float:
        import jax

        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out.blocks)
        return time.perf_counter() - t0

    measure = _measure_default if measure is None else measure
    costs: dict[str, float] = {}
    spent = 0.0
    runs = 0
    with obs.span("tuner.probe", sr=sr.name, dim=int(E.nrows), op="spmm"):
        for backend in cands:
            if costs and spent >= budget_s:
                if obs.ENABLED:
                    obs.count("tuner.probe.budget_exhausted")
                break

            def run(backend=backend):
                return spmm_mod.dist_spmm_ell(sr, E, X, backend=backend)

            try:
                run()  # compile + warm (untimed)
                dt = float(measure(run))
            except Exception:
                if obs.ENABLED:
                    obs.count("tuner.probe.errors", tier=backend)
                continue
            costs[backend] = dt
            spent += dt
            runs += 1
            if obs.ENABLED:
                obs.count("tuner.probe.runs", tier=backend)
    if store is not None:
        store.record_probe(runs, spent)
    if obs.ENABLED:
        obs.count("tuner.probe.seconds", spent)
    if not costs:
        return None
    winner = min(costs, key=costs.get)
    if obs.ENABLED:
        obs.count("tuner.probe.winner", tier=winner)
    rec = PlanRecord(
        tier=winner, cost_s=costs[winner], source="probe",
        probe_dim=int(E.nrows),
    )
    if store is not None and key is not None:
        store.put(key, rec)
    return rec
