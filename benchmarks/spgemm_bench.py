"""SUMMA SpGEMM microbenchmark (≈ ReleaseTests/MultTiming.cpp).

A·A on an R-MAT matrix with pre-sized capacities so the timed section is
the compiled SUMMA only (a barrier readback closes the timed window). Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SCALE = int(os.environ.get("BENCH_SCALE", "14"))
REPS = int(os.environ.get("BENCH_REPS", "3"))
# Square process grid side: BENCH_PR=2 runs the DISTRIBUTED SUMMA on a
# pr x pr virtual CPU mesh (XLA host-device-count, the conftest.py
# pattern) — the large-scale distributed capture knob (r9's scale-17
# record). 1 (default) keeps the single-device protocol unchanged.
PR = int(os.environ.get("BENCH_PR", "1"))
# Windowed-tier schedule: BENCH_RING=1 runs the carousel
# (neighbor-rotation) schedule, BENCH_PIPELINE=0 pins its serial-chain
# control — the pipelined-vs-unpipelined A/B of ISSUE 7.
RING = os.environ.get("BENCH_RING", "0") == "1"
PIPELINE = os.environ.get("BENCH_PIPELINE", "1") == "1"
# Input pattern: rmat (default) | banded — a |i-j| <= n/64 band whose
# A² support leaves most 2D windows symbolically EMPTY (the packed-
# launch ratio showcase; R-MAT support is too uniform to skip much).
PATTERN = os.environ.get("BENCH_PATTERN", "rmat")
# Windowed multi-device dispatch: fused (default, one shard_map graph)
# | blocked (one small program per row block — the live-set bound that
# fits scale-17+ tiles in RAM; scatter backend only).
DISPATCH = os.environ.get("BENCH_DISPATCH", "fused")
# esc | mxu | scan | scanphased | windowed | auto  (auto = the tier
# router's choice, sized host-side like every other kernel here)
KERNEL = os.environ.get("BENCH_KERNEL", "esc")
PHASES = int(os.environ.get("BENCH_PHASES", "8"))  # scanphased only
OCAP = os.environ.get("BENCH_OCAP")  # override out_capacity (mxu sparsify
# cost scales with it: searchsorted queries per slot; scan: accumulator
# slots — sized from the exact host symbolic out-nnz when unset)
# BENCH_GOLDEN=1 (default): after timing, verify the result EXACTLY
# against the scipy A² golden (nnz and integer count values) — the same
# golden the ESC path is validated against, so agreement here is
# agreement with ESC. =0 skips (saves the host product + readback).
GOLDEN = os.environ.get("BENCH_GOLDEN", "1") == "1"
BLOCK_ROWS = int(os.environ.get("BENCH_BLOCK_ROWS", "0"))  # windowed tier
BLOCK_COLS = int(os.environ.get("BENCH_BLOCK_COLS", "0"))  # 2D dot backend
# R-MAT edge factor: flops (and the sort-based tiers' cost) grow with
# it while dense n^3 work is fixed, so sweeping it traces the
# scan -> windowed-dot crossover at one scale (results/r7).
EDGEFACTOR = int(os.environ.get("BENCH_EDGEFACTOR", "8"))
# windowed-dot stage-product precision (parallel/spgemm._mxu_dot):
# f32 | bf16 | bf16x3.  f32 default — exact everywhere; on the chip
# bf16 is the fast mode (exact for 0/1 counts < 2^24).
DOT_MODE = os.environ.get("BENCH_DOT_MODE", "f32")
# --- round-10 plan-store knobs ---------------------------------------------
# BENCH_PLAN_STORE=dir points the measured-plan store at `dir` ("0"
# disables) — it simply sets COMBBLAS_PLAN_STORE before the library
# loads, so BENCH_KERNEL=auto resolves through the store (tuner
# precedence: store > env > probe > heuristic; probing via
# COMBBLAS_TUNER_PROBE=1 runs IN-PROCESS before the timed section — on
# readback-poisoned chips keep probing in a separate process, which the
# A/B scenario below does by construction).
if os.environ.get("BENCH_PLAN_STORE") is not None:
    os.environ["COMBBLAS_PLAN_STORE"] = os.environ["BENCH_PLAN_STORE"]
# BENCH_PLAN_RECORD=1: write THIS run's measured (kernel, knobs, cost)
# back into the store (source="bench") — how operators seed a fleet
# store from forced-kernel sweeps.
PLAN_RECORD = os.environ.get("BENCH_PLAN_RECORD", "0") == "1"
# BENCH_TUNER_AB=1: the warm-vs-cold-process scenario — three children
# of this same script at the current BENCH_* settings: `heuristic`
# (store disabled), `cold` (fresh store + probing: pays the probe,
# writes the winner), `warm` (same store: hits the plan, ZERO probe
# runs). Prints one combined JSON line.
TUNER_AB = os.environ.get("BENCH_TUNER_AB", "0") == "1"
# BENCH_FIRST_TOUCH=1 (windowed): time the FIRST mult call — compile
# included — instead of the warm loop; with BENCH_PR>1 and
# BENCH_DISPATCH=fused|blocked this is the bounded-compile A/B of the
# building-block decomposition (ISSUE 8 acceptance).
FIRST_TOUCH = os.environ.get("BENCH_FIRST_TOUCH", "0") == "1"
_EFTAG = f"ef{EDGEFACTOR}" if EDGEFACTOR != 8 else ""
_GRIDTAG = f"_p{PR}x{PR}" if PR > 1 else ""
_RINGTAG = ("_ring" if PIPELINE else "_ringserial") if RING else ""


def tuner_ab():
    """BENCH_TUNER_AB=1: heuristic / cold-probe / warm-store children
    (one process each — the warm child is the 'fresh replica with a
    shipped plan store' of the acceptance gate).  Asserts in-JSON that
    the warm child routed from the store with zero probe runs."""
    import subprocess
    import tempfile

    store_dir = os.environ.get("BENCH_PLAN_STORE") or tempfile.mkdtemp(
        prefix="bench-plans-"
    )

    def child(tag, env_over):
        env = dict(os.environ)
        env.pop("BENCH_TUNER_AB", None)
        # the child re-applies BENCH_PLAN_STORE over COMBBLAS_PLAN_STORE
        # at import — strip it so the per-child store assignment below
        # is authoritative (else the heuristic child would route through
        # a pre-warmed store and the baseline would be a second warm run)
        env.pop("BENCH_PLAN_STORE", None)
        env.setdefault("BENCH_GOLDEN", "0")  # A/B times routing, not golden
        env["BENCH_KERNEL"] = "auto"
        env.update(env_over)
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True,
        )
        lines = [
            ln for ln in p.stdout.strip().splitlines()
            if ln.startswith("{")
        ]
        rec = json.loads(lines[-1]) if lines else {}
        rec.pop("obs_jsonl", None)
        rec["_tag"] = tag
        rec["_rc"] = p.returncode
        if p.returncode:
            rec["_stderr"] = p.stderr[-2000:]
        return rec

    heur = child("heuristic", {"COMBBLAS_PLAN_STORE": "0"})
    cold = child("cold", {
        "COMBBLAS_PLAN_STORE": store_dir, "COMBBLAS_TUNER_PROBE": "1",
    })
    warm = child("warm", {
        "COMBBLAS_PLAN_STORE": store_dir, "COMBBLAS_TUNER_PROBE": "1",
    })
    warm_ms = warm.get("ms_per_spgemm") or 0
    heur_ms = heur.get("ms_per_spgemm") or 0
    out = {
        "metric": f"spgemm_tuner_ab_{PATTERN}_scale{SCALE}{_EFTAG}"
                  f"{_GRIDTAG}_warm_ms",
        "value": warm_ms,
        "unit": "ms",
        "store_dir": store_dir,
        "heuristic": heur,
        "cold": cold,
        "warm": warm,
        # the acceptance gates, evaluated in-line:
        "warm_store_hit": warm.get("plan_source") == "store",
        "cold_probe_runs": (cold.get("tuner") or {}).get(
            "probe_runs", -1
        ),
        "warm_probe_runs": (warm.get("tuner") or {}).get(
            "probe_runs", -1
        ),
        "warm_vs_heuristic_speedup": (
            round(heur_ms / warm_ms, 3) if warm_ms and heur_ms else None
        ),
    }
    print(json.dumps(out), flush=True)


def main():
    if TUNER_AB:
        return tuner_ab()
    if PR > 1 and os.environ.get("JAX_PLATFORMS", "") != "tpu":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={PR * PR}"
        )
    import jax

    if PR > 1 and os.environ.get("JAX_PLATFORMS", "") != "tpu":
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from combblas_tpu import PLUS_TIMES, obs
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spgemm import (
        summa_capacities_host,
        summa_spgemm,
        summa_stage_flops_host,
    )
    from combblas_tpu.parallel.spmat import SpParMat
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    # BENCH_OBS=1: per-process JSONL sidecar (the bench.py convention) —
    # carries the tier-router counters (spgemm.auto.tier,
    # spgemm.windowed.windows_skipped, spgemm.auto.mask_density)
    obs.enable_sidecar(f"spgemm-{KERNEL}")

    grid = Grid.make(PR, PR)
    n = 1 << SCALE
    if PATTERN == "banded":
        bw = max(n // 64, 1)
        ri = np.arange(n, dtype=np.int64)
        rows = np.concatenate(
            [ri for _ in range(-3, 4)]
        )
        cols = np.concatenate(
            [np.clip(ri + o * max(bw // 3, 1), 0, n - 1)
             for o in range(-3, 4)]
        )
    else:
        assert PATTERN == "rmat", PATTERN
        rows, cols = rmat_symmetric_coo_host(5, SCALE, EDGEFACTOR)
    key = rows * np.int64(n) + cols
    uniq = np.unique(key)
    ru, cu = uniq // n, uniq % n
    # Symbolic sizing on HOST from the COO: no device readback before
    # the timed launches.
    per_stage = summa_stage_flops_host(grid, ru, cu, ru, cu, n, n, n)
    # true scalar multiplies for the MFLOP/s numerator (per_stage above is
    # chunk-padded for capacity sizing)
    flops = int(
        summa_stage_flops_host(
            grid, ru, cu, ru, cu, n, n, n, padded=False
        ).sum()
    )
    fcap, ocap = summa_capacities_host(
        grid, ru, cu, ru, cu, n, n, n, per_stage=per_stage
    )
    # BENCH_KERNEL=auto: resolve the router's tier HERE (host counts
    # only — no device readback) and run that kernel below; the metric
    # name keeps the requested "auto" and the JSON carries the tier.
    # Round 10: resolution follows the tuner precedence — plan store >
    # env > probe (opt-in) > heuristic — via the SAME key builder the
    # library router uses, so a store warmed here routes spgemm_auto
    # and vice versa.
    A = SpParMat.from_global_coo(
        grid, ru, cu, np.ones(len(ru), np.float32), n, n
    )
    kernel = KERNEL
    tier = None
    backend = None
    plan_source = None
    plan_key = None
    store = None
    from combblas_tpu.tuner import store as tuner_store

    store = tuner_store.get_store()
    if KERNEL in ("auto", "windowed"):
        from combblas_tpu.parallel.spgemm import resolve_spgemm_backend

        # COMBBLAS_SPGEMM_BACKEND=dot forces the 2D MXU path (the TPU
        # stand-in run on this CPU image); default follows the platform
        backend = resolve_spgemm_backend()
    if store is not None:
        from combblas_tpu.parallel.spgemm import (
            resolve_spgemm_backend as _resolve_be,
        )

        # key under the RESOLVED backend even for forced kernels, so a
        # recorded plan and the library router agree on the key
        plan_key = tuner_store.plan_key_from_counts(
            "plus_times", n, n, n, len(ru), len(ru),
            backend or _resolve_be(), f"{grid.pr}x{grid.pc}",
        )
    plan_rec = None
    if KERNEL == "auto":
        # ONE walk of the store > env > probe > heuristic chain,
        # shared with spgemm3d_bench and vetted like the library
        # router (round-11 satellite: the inline copies skipped the
        # record vetting)
        from combblas_tpu.tuner.resolve import resolve_tier

        def _probe():
            from combblas_tpu.tuner.probe import probe_spgemm

            return probe_spgemm(
                PLUS_TIMES, A, A, backend=backend, store=store,
                key=plan_key,
                host_coo_a=(ru, cu, np.ones(len(ru), np.float32)),
            )

        def _heuristic():
            from combblas_tpu.parallel.spgemm import (
                choose_tier_from_counts,
            )

            lrA_, lcB_ = grid.local_rows(n), grid.local_cols(n)
            return choose_tier_from_counts(
                PLUS_TIMES, max(lrA_, lcB_), lrA_ * lcB_, grid.pr,
                float(flops), backend, k_dim=grid.local_rows(n),
                n_dim=lcB_,
            )

        tier, plan_source, plan_rec = resolve_tier(
            plan_key, op="spgemm",
            allowed=("mxu", "windowed", "scan", "esc"),
            heuristic=_heuristic, probe=_probe, store=store,
        )
        obs.count("spgemm.auto.tier", tier=tier, sr="plus_times")
        kernel = tier
    else:
        plan_source = "arg"  # BENCH_KERNEL forced this rung

    def provenance(**knobs):
        """plan provenance fields for the output JSON (satellite 2)."""
        p = {
            "plan_source": plan_source,
            "plan": {"tier": tier or kernel, "backend": backend,
                     **knobs},
        }
        if store is not None:
            p["tuner"] = store.stats()
        return p

    def record_plan(ms_per_spgemm, block_rows=None, block_cols=None):
        """BENCH_PLAN_RECORD=1: persist this run's measured plan —
        only if it BEATS the remembered cost (a forced-kernel seeding
        sweep must converge on the cheapest plan regardless of sweep
        order)."""
        if not PLAN_RECORD or store is None or plan_key is None:
            return
        if kernel not in ("mxu", "windowed", "scan", "esc"):
            return  # scanphased is a bench-only protocol, not a tier
        prev = store.peek(plan_key)
        if (
            prev is not None
            and prev.cost_s is not None
            and prev.cost_s <= ms_per_spgemm / 1e3
        ):
            return
        store.put(plan_key, tuner_store.PlanRecord(
            tier=kernel, block_rows=block_rows, block_cols=block_cols,
            ring=RING, pipeline=PIPELINE,
            # record the dispatch the cost was MEASURED under (None
            # would replay fused measurements as auto->blocked)
            dispatch=DISPATCH if kernel == "windowed" else None,
            cost_s=ms_per_spgemm / 1e3, source="bench",
        ))
    if kernel == "scan":
        # exact output structure on host: out_capacity = nnz(A^2) — the
        # scan variant's accumulator scales with the OUTPUT, which is what
        # lets scale 16 fit in HBM (the round-2 all-stages-live ESC
        # faulted the device there).
        if OCAP:
            ocap = int(OCAP)
        else:
            from scipy import sparse

            S = sparse.csr_matrix(
                (np.ones(len(ru), np.float32), (ru, cu)), shape=(n, n)
            )
            nnz_out = int((S @ S).nnz)
            ocap = 1 << int(np.ceil(np.log2(max(nnz_out, 2) * 1.05)))

    # All REPS chained inside ONE launch (per-launch dispatch measured
    # ~105 ms-1.8 s in rounds 2-5 on a machine that is gone,
    # benchmarks/results/instrument_r2*; not re-measured).
    import dataclasses

    import jax.numpy as jnp
    from jax import lax

    if kernel == "windowed":
        # Round 6: the auto-tiered general sparse-output path. Sizing is
        # HOST-ONLY (no device readback): the row-block symbolic pass + plan
        # come from the COO before any upload; "auto" additionally runs
        # the router's gate over the same host counts and records the
        # chosen tier through obs.
        from combblas_tpu.parallel.spgemm import (
            WINDOWED_CHUNK_W,
            _pad128,
            default_block_cols,
            default_block_rows,
            local_spgemm_windowed,
            panel_cap_from_bnnz,
            summa_rowblock_flops_host,
            summa_spgemm_windowed,
            summa_window_bnnz_host,
            summa_window_flops_host,
            windowed_plan,
            windowed_plan_2d,
        )

        lrA = grid.local_rows(n)
        lcB = grid.local_cols(n)
        # KERNEL=auto already resolved (and obs-counted) the tier above;
        # a direct BENCH_KERNEL=windowed request is its own tier.
        # Geometry precedence mirrors the library: bench knob > the
        # store record's measured shape > the kernel default.
        tier = tier or "windowed"
        rec_br = plan_rec.block_rows if plan_rec is not None else None
        rec_bc = plan_rec.block_cols if plan_rec is not None else None
        block_rows = BLOCK_ROWS or rec_br or default_block_rows(
            lrA, lcB
        )
        extra = {}
        if backend == "dot":
            # 2D B-column-windowed MXU form, sized host-only (no device
            # readback): the 2D symbolic pass, the plan, and the panel slice
            # capacity all come from the COO before any upload.
            block_cols = BLOCK_COLS or rec_bc or default_block_cols(
                grid.local_rows(n), lcB
            )
            # one TRUE-counts pass only: the dot backend never consumes
            # flop caps (no chunked expansion), so the chunk_w-padded
            # einsum would be dead sizing work
            pt = summa_window_flops_host(
                grid, ru, cu, ru, cu, n, n, n, block_rows, block_cols,
                chunk_w=0,
            )
            flop_caps, out_caps, skip = windowed_plan_2d(
                None, pt, block_rows, block_cols, lrA, lcB
            )
            panel_cap = panel_cap_from_bnnz(
                summa_window_bnnz_host(grid, ru, cu, n, n, block_cols),
                len(ru),
            )
            nskip = sum(sum(row) for row in skip)
            obs.count("spgemm.windowed.col_windows_skipped", nskip)
            from combblas_tpu.parallel.spgemm import packed_windows_2d

            npk = len(packed_windows_2d(skip))
            ntot = sum(len(row) for row in skip)
            obs.count("spgemm.windowed.windows_packed", npk)
            obs.gauge(
                "spgemm.windowed.pack_ratio", npk / ntot if ntot else 0.0
            )
            obs.gauge(
                "spgemm.windowed.col_windows", len(skip[0]) if skip else 0
            )
            obs.gauge(
                "spgemm.windowed.panel_cells",
                _pad128(grid.local_rows(n)) * _pad128(block_cols),
            )
            obs.gauge("spgemm.windowed.blocks", len(skip))
            extra = {
                "backend": "dot",
                "mode": DOT_MODE,
                "block_cols": block_cols,
                "col_windows": len(skip[0]) if skip else 0,
                "col_windows_skipped": int(nskip),
                "windows_packed": int(npk),
                "windows_total": int(ntot),
                "pack_ratio": round(npk / ntot, 4) if ntot else 0.0,
                "panel_cap": int(panel_cap),
                "panel_cells": int(
                    _pad128(grid.local_rows(n)) * _pad128(block_cols)
                ),
            }

            def mult(a):
                if grid.size == 1:
                    return local_spgemm_windowed(
                        PLUS_TIMES, a, a, block_rows=block_rows,
                        flop_caps=flop_caps, out_caps=out_caps,
                        skip=skip, backend="dot", block_cols=block_cols,
                        panel_cap=panel_cap, mode=DOT_MODE,
                    )
                return summa_spgemm_windowed(
                    PLUS_TIMES, a, a, block_rows=block_rows,
                    flop_caps=flop_caps, out_caps=out_caps, skip=skip,
                    backend="dot", mode=DOT_MODE,
                    chunk_w=WINDOWED_CHUNK_W, block_cols=block_cols,
                    panel_cap=panel_cap, ring=RING, pipeline=PIPELINE,
                )
        else:
            pb = summa_rowblock_flops_host(
                grid, ru, cu, ru, cu, n, n, n, block_rows,
                chunk_w=WINDOWED_CHUNK_W,
            )
            pt = summa_rowblock_flops_host(
                grid, ru, cu, ru, cu, n, n, n, block_rows, chunk_w=0
            )
            flop_caps, out_caps, skip = windowed_plan(
                pb, pt, block_rows, lrA, lcB
            )
            obs.count("spgemm.windowed.windows_skipped", sum(skip))
            from combblas_tpu.parallel.spgemm import packed_windows

            npk = len(packed_windows(skip))
            obs.count("spgemm.windowed.windows_packed", npk)
            obs.gauge(
                "spgemm.windowed.pack_ratio",
                npk / len(skip) if skip else 0.0,
            )
            obs.gauge("spgemm.windowed.blocks", len(skip))
            # same quantity as the library emitter (parallel/spgemm.py:
            # spgemm_windowed): raw symbolic output bound over dense cells
            obs.gauge(
                "spgemm.auto.mask_density",
                float(np.asarray(pt).sum(axis=1).max(axis=(-1, -2)).sum())
                / max(lrA * lcB, 1),
            )
            extra = {
                "windows_packed": int(npk),
                "windows_total": len(skip),
                "pack_ratio": (
                    round(npk / len(skip), 4) if skip else 0.0
                ),
            }

            if DISPATCH == "blocked" and grid.size > 1:
                # per-block programs share compiles when caps match:
                # pow2-round so most blocks hit one executable
                rnd = lambda x: 1 << (max(int(x), 1) - 1).bit_length()
                flop_caps = tuple(rnd(fcp) for fcp in flop_caps)
                out_caps = tuple(rnd(ocp) for ocp in out_caps)
                extra["dispatch"] = "blocked"

            def mult(a):
                # grid 1x1 here: the per-block-program fast path (the
                # fused shard_map graph measures >2x slower on XLA:CPU)
                if grid.size == 1:
                    return local_spgemm_windowed(
                        PLUS_TIMES, a, a, block_rows=block_rows,
                        flop_caps=flop_caps, out_caps=out_caps, skip=skip,
                        chunk_w=WINDOWED_CHUNK_W,
                    )
                if DISPATCH == "blocked":
                    from combblas_tpu.parallel.spgemm import (
                        summa_spgemm_windowed_blocked,
                    )

                    return summa_spgemm_windowed_blocked(
                        PLUS_TIMES, a, a, block_rows=block_rows,
                        flop_caps=flop_caps, out_caps=out_caps,
                        skip=skip, chunk_w=WINDOWED_CHUNK_W,
                    )
                return summa_spgemm_windowed(
                    PLUS_TIMES, a, a, block_rows=block_rows,
                    flop_caps=flop_caps, out_caps=out_caps, skip=skip,
                    backend="scatter", chunk_w=WINDOWED_CHUNK_W,
                    ring=RING, pipeline=PIPELINE,
                )

        if FIRST_TOUCH:
            # FIRST call, compile included: the bounded first-touch
            # gate of the building-block decomposition (run once per
            # process with BENCH_DISPATCH=fused, once with =blocked)
            t0 = time.perf_counter()
            C, ov = mult(A)
            jax.block_until_ready(C.vals)
            t_first = time.perf_counter() - t0
            out = {
                "metric": (
                    f"spgemm_AxA_{PATTERN}_scale{SCALE}{_EFTAG}"
                    f"{_GRIDTAG}_windowed_firsttouch_{DISPATCH}_s"
                ),
                "value": round(t_first, 3),
                "unit": "s",
                "dispatch": DISPATCH,
                "block_rows": block_rows,
                "blocks": len(skip),
                "out_nnz": int(jax.device_get(C.getnnz())),
                "grid": f"{grid.pr}x{grid.pc}",
                **provenance(block_rows=block_rows),
            }
            if obs.ENABLED:
                out["obs_jsonl"] = obs.dump_jsonl()
            print(json.dumps(out))
            return
        C, ov = mult(A)  # warmup/compile
        jax.block_until_ready(C.vals)
        time.sleep(3)
        t0 = time.perf_counter()
        for _ in range(REPS):
            C, ov = mult(A)
        nnz_v = int(jax.device_get(C.getnnz()))  # barrier
        dt = time.perf_counter() - t0
        record_plan(
            dt / REPS * 1e3, block_rows=block_rows,
            block_cols=(
                extra.get("block_cols") if backend == "dot" else None
            ),
        )
        out = {
            "metric": (
                f"spgemm_AxA_{PATTERN}_scale{SCALE}{_EFTAG}{_GRIDTAG}"
                f"_{KERNEL}{'dot' if backend == 'dot' else ''}"
                f"{_RINGTAG}_MFLOPs"
            ),
            "value": round(flops * 2 * REPS / dt / 1e6, 2),
            "unit": "MFLOP/s",
            "flops": int(flops),
            "ms_per_spgemm": round(dt / REPS * 1e3, 2),
            "out_nnz": nnz_v,
            "overflow": int(jax.device_get(ov)),
            "tier": tier,
            "grid": f"{grid.pr}x{grid.pc}",
            "ring": RING,
            "pipeline": PIPELINE,
            "block_rows": block_rows,
            "blocks": len(skip),
            "windows_skipped": (
                int(sum(skip)) if backend != "dot"
                else extra["col_windows_skipped"]
            ),
            **extra,
            **provenance(block_rows=block_rows),
        }
        if GOLDEN:
            # EXACT agreement with the A² golden: 0/1 adjacency counts
            # are integers < 2^24, so the comparison is bit-exact — the
            # same golden the ESC path reproduces (MultTest role).
            from scipy import sparse

            tr, tc_, tv = (
                np.asarray(jax.device_get(x))
                for x in (C.rows, C.cols, C.vals)
            )
            lr_, lc_ = C.local_rows, C.local_cols
            gr_, gc_, gv_ = [], [], []
            for i in range(grid.pr):  # stitch every tile (PR > 1)
                for j in range(grid.pc):
                    live = tr[i, j] < lr_
                    gr_.append(tr[i, j][live].astype(np.int64) + i * lr_)
                    gc_.append(tc_[i, j][live].astype(np.int64) + j * lc_)
                    gv_.append(tv[i, j][live])
            got = sparse.csr_matrix(
                (np.concatenate(gv_),
                 (np.concatenate(gr_), np.concatenate(gc_))),
                shape=(n, n),
            )
            got.sum_duplicates()
            S = sparse.csr_matrix(
                (np.ones(len(ru), np.float32), (ru, cu)), shape=(n, n)
            )
            P = S @ S
            P.sort_indices()
            got.sort_indices()
            out["golden_nnz"] = int(P.nnz)
            out["golden_nnz_match"] = bool(got.nnz == P.nnz)
            out["golden_exact"] = bool(
                got.nnz == P.nnz
                and np.array_equal(got.indptr, P.indptr)
                and np.array_equal(got.indices, P.indices)
                and np.array_equal(got.data, P.data)
            )
        if obs.ENABLED:
            out["obs_jsonl"] = obs.dump_jsonl()
        print(json.dumps(out))
        return
    if kernel == "scanphased":
        # MemEfficientSpGEMM pattern at benchmark level: B's columns split
        # into flop-BALANCED phases (host symbolic), every phase runs the
        # output-bounded scan kernel with ONE shared capacity set (single
        # compile), all sizing on host before any launch.
        # This is what fits scale 16 in HBM: the single-stage expansion
        # (~420M slots x3 arrays, doubled by the sort) exhausts the 16G
        # device; per-phase working sets are PHASES-fold smaller.
        from scipy import sparse as _sp

        from combblas_tpu.parallel.spgemm import summa_spgemm_scan

        deg = np.bincount(ru, minlength=n)
        colflops = deg[cu]  # flops contributed by each entry (B-row walk)
        # order entries by column; split columns at equal-flop boundaries
        order = np.argsort(cu, kind="stable")
        cum = np.cumsum(colflops[order])
        co = cu[order]
        bounds = [0]
        for ph in range(1, PHASES):
            t = cum[-1] * ph / PHASES
            b = min(int(np.searchsorted(cum, t)), len(order) - 1)
            # snap DOWN to the column boundary: a split column would be
            # produced by two phases and double-count its outputs
            bounds.append(int(np.searchsorted(co, co[b], side="left")))
        bounds.append(len(order))
        Bs = []
        fcapp = ocapp = 1
        S = _sp.csr_matrix(
            (np.ones(len(ru), np.float32), (ru, cu)), shape=(n, n)
        )
        # ONE host product: every phase output is a column range of it
        # (phases are column-disjoint), so per-phase out-nnz reads off the
        # CSC indptr instead of PHASES more host SpGEMMs
        Pcsc = (S @ S).tocsc()
        col_nnz = np.diff(Pcsc.indptr)
        for ph in range(PHASES):
            sel = order[bounds[ph]:bounds[ph + 1]]
            rp, cp = ru[sel], cu[sel]
            per = summa_stage_flops_host(grid, ru, cu, rp, cp, n, n, n)
            fcapp = max(fcapp, int(per.max() * 1.05) + 1)
            if len(cp):
                lo, hi = int(cp.min()), int(cp.max()) + 1
                ph_nnz = int(col_nnz[lo:hi].sum())
                ocapp = max(ocapp, int(ph_nnz * 1.05) + 1)
            Bs.append(
                SpParMat.from_global_coo(
                    grid, rp, cp, np.ones(len(rp), np.float32), n, n
                )
            )
        rnd = lambda x: 1 << (x - 1).bit_length()
        fcapp, ocapp = rnd(fcapp), rnd(ocapp)
        # equalize slot capacities so ALL phases share one compiled program
        cap_b = rnd(max(int(b.capacity) for b in Bs))
        Bs = [b.with_capacity(cap_b) for b in Bs]
        A = A.shrink_to_fit()

        def phase_mult(a, b):
            return summa_spgemm_scan(
                PLUS_TIMES, a, b, flop_capacity=fcapp, out_capacity=ocapp
            )

        outs = [phase_mult(A, b) for b in Bs]  # warmup/compile (cached)
        jax.block_until_ready(outs[-1][0].vals)
        time.sleep(3)
        t0 = time.perf_counter()
        nnz_total = jnp.int32(0)
        ov_total = jnp.int32(0)
        for _ in range(REPS):
            for b in Bs:
                Cp, ov = phase_mult(A, b)
                nnz_total = nnz_total + Cp.getnnz()
                ov_total = jnp.maximum(ov_total, ov)
        nnz_v = int(jax.device_get(nnz_total)) // REPS  # barrier
        dt = time.perf_counter() - t0
        print(
            json.dumps(
                {
                    "metric": f"spgemm_AxA_{PATTERN}_scale{SCALE}{_EFTAG}_scanphased{PHASES}_MFLOPs",
                    "value": round(flops * 2 * REPS / dt / 1e6, 2),
                    "unit": "MFLOP/s",
                    "flops": int(flops),
                    "ms_per_spgemm": round(dt / REPS * 1e3, 2),
                    "out_nnz": nnz_v,
                    "overflow": int(jax.device_get(ov_total)),
                }
            )
        )
        return
    if kernel == "scan":
        from combblas_tpu.parallel.spgemm import summa_spgemm_scan

        overflow_dev = None

        @jax.jit
        def chain(mat):
            def body(_, carry):
                a = dataclasses.replace(mat, vals=mat.vals + carry * 0)
                C, ov = summa_spgemm_scan(
                    PLUS_TIMES, a, a,
                    flop_capacity=fcap, out_capacity=ocap,
                )
                return C.vals[0, 0, 0] * 0 + ov.astype(jnp.float32) * 0

            return lax.fori_loop(0, REPS, body, jnp.float32(0))

        out = chain(A)  # warmup/compile
        jax.block_until_ready(out)
        time.sleep(3)
        t0 = time.perf_counter()
        out = chain(A)
        _ = float(jax.device_get(out))  # barrier
        dt = time.perf_counter() - t0
        C, overflow_dev = summa_spgemm_scan(
            PLUS_TIMES, A, A, flop_capacity=fcap, out_capacity=ocap
        )
    elif kernel == "mxu":
        from combblas_tpu.parallel.spgemm import summa_spgemm_mxu

        # round 4: bf16 stage products (13.3 TFLOP/s, exact for the 0/1
        # inputs here) + the windowed output-driven extraction; BENCH_MXU_MODE
        # picks f32/bf16/bf16x3 (see parallel/spgemm._mxu_dot)
        mxu_mode = os.environ.get("BENCH_MXU_MODE", "bf16")
        mxu_ocap = int(OCAP) if OCAP else ocap
        mxu_overflow = None

        def mult(a):
            nonlocal mxu_overflow
            C, mxu_overflow = summa_spgemm_mxu(
                PLUS_TIMES, a, a, out_capacity=mxu_ocap, mode=mxu_mode
            )
            return C

        # The dense accumulators are GBs; a fori_loop chain double-buffers
        # them past HBM (device fault). Kernel time (seconds) dwarfs the
        # per-launch dispatch, so separate launches time honestly here.
        C = mult(A)  # warmup/compile
        jax.block_until_ready(C.vals)
        time.sleep(3)
        t0 = time.perf_counter()
        for _ in range(REPS):
            C = mult(A)
        _ = float(jax.device_get(C.vals[0, 0, 0]))  # barrier
        dt = time.perf_counter() - t0
    else:

        def mult(a):
            # BENCH_RING=1: the carousel (neighbor-rotation) schedule —
            # the pre-round-9 serial carousel is BENCH_KERNEL=esc with
            # ring on the old commit; this one is now stage-pipelined
            return summa_spgemm(
                PLUS_TIMES, a, a, flop_capacity=fcap, out_capacity=ocap,
                ring=RING,
            )

        @jax.jit
        def chain(mat):
            def body(_, carry):
                a = dataclasses.replace(mat, vals=mat.vals + carry * 0)
                C = mult(a)
                return C.vals[0, 0, 0] * 0  # serializing dependence

            return lax.fori_loop(0, REPS, body, jnp.float32(0))

        out = chain(A)  # warmup/compile
        jax.block_until_ready(out)
        time.sleep(3)
        t0 = time.perf_counter()
        out = chain(A)
        _ = float(jax.device_get(out))  # barrier
        dt = time.perf_counter() - t0
        C = mult(A)
    record_plan(dt / REPS * 1e3)
    out = {
        "metric": f"spgemm_AxA_{PATTERN}_scale{SCALE}{_EFTAG}{_GRIDTAG}_{KERNEL}{_RINGTAG}_MFLOPs",
        "value": round(flops * 2 * REPS / dt / 1e6, 2),
        "unit": "MFLOP/s",
        "flops": int(flops),
        "ms_per_spgemm": round(dt / REPS * 1e3, 2),
        "out_nnz": int(jax.device_get(C.getnnz())),
        # nonzero = capacity truncated the product; numbers invalid
        "overflow": (
            int(jax.device_get(mxu_overflow))
            if kernel == "mxu"
            else int(jax.device_get(overflow_dev))
            if kernel == "scan"
            else 0
        ),
        **provenance(),
    }
    from combblas_tpu import obs as _obs

    if _obs.ENABLED:
        out["obs_jsonl"] = _obs.dump_jsonl()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
