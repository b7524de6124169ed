"""Application-level single-chip benchmarks (BASELINE.md tracked configs).

Same protocol as bench.py (host build + host symbolic sizing, one
upload, one timed launch closed by a scalar readback). Prints one
JSON line per app. One app per process (fresh-process rule).

APP=pagerank: K power iterations of the PLUS_TIMES ELL SpMV with teleport
(the PageRank.cpp loop, :126-157) fused into one launch.
APP=ppr: W personalized-PageRank chains in ONE program
(``pagerank_batch`` — the multi-root amortization; compare s/iter
against APP=pagerank to see the per-index gather cost split W ways).
APP=tc: L = tril(A); count = sum((L·L) .* L) — TC.cpp:104-116 — host
symbolic sizing + one fused launch (no mid-run readbacks).
APP=cc: FastSV connected components (one while_loop launch).
APP=lacc: LACC star hooking/shortcutting (one while_loop launch).
APP=sssp: Bellman-Ford MIN_PLUS fixed point (one while_loop launch).
APP=sssp_batch: W-source Bellman-Ford chains in ONE program
(``sssp_batch`` — the same W-lane gather amortization as APP=ppr).
APP=bc: batched Brandes from BENCH_ROOTS sources (host loop per level —
the reference's while(fringe.getnnz()) shape; per-level sizing readbacks
degrade this chip (D2H poison), recorded as-is).
APP=mcl: BENCH_ITERS expand/prune/inflate iterations in ONE launch with
frozen host-sized capacities (the chaos_every machinery); overflow flags
checked after timing.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

APP = os.environ.get("BENCH_APP", "pagerank")
SCALE = int(os.environ.get("BENCH_SCALE", "18"))
ITERS = int(os.environ.get("BENCH_ITERS", "16"))


def _graph(scale, ef=16):
    import numpy as np

    from combblas_tpu.utils.refgen21 import graph500_edges_native

    n = 1 << scale
    src, dst = graph500_edges_native(scale, edgefactor=ef, userseed=11)
    keep = src != dst
    r = np.concatenate([src[keep], dst[keep]])
    c = np.concatenate([dst[keep], src[keep]])
    u = np.unique(r * np.int64(n) + c)
    return (u // n).astype(np.int64), (u % n).astype(np.int64), n


def bench_pagerank():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from combblas_tpu import PLUS_TIMES
    from combblas_tpu.parallel.ellmat import EllParMat, dist_spmv_ell
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.vec import DistVec

    r, c, n = _graph(SCALE)
    grid = Grid.make(1, 1)
    deg = np.bincount(c, minlength=n).astype(np.float32)
    # column-stochastic edge weights (out-degree normalization)
    w = (1.0 / np.maximum(deg, 1.0))[c].astype(np.float32)
    E = EllParMat.from_host_coo(grid, r, c, w, n, n)
    x0 = DistVec.from_global(
        grid, np.full(n, 1.0 / n, np.float32), align="col"
    )

    @jax.jit
    def power(ell, xb):
        def body(_, xb):
            xv = DistVec(blocks=xb, length=n, align="col", grid=grid)
            y = dist_spmv_ell(PLUS_TIMES, ell, xv)
            yb = 0.85 * y.blocks + 0.15 / n
            return DistVec(
                blocks=yb, length=n, align="row", grid=grid
            ).realign("col").blocks

        return lax.fori_loop(0, ITERS, body, xb)

    out = power(E, x0.blocks)
    jax.block_until_ready(out)
    time.sleep(3)
    t0 = time.perf_counter()
    out = power(E, x0.blocks)
    _ = float(jax.device_get(out[0, 0]))
    dt = time.perf_counter() - t0
    nnz = len(r)
    print(
        json.dumps(
            {
                "metric": f"pagerank_rmat_scale{SCALE}_GFLOPs",
                "value": round(nnz * 2 * ITERS / dt / 1e9, 3),
                "unit": "GFLOP/s",
                "ms_per_iter": round(dt / ITERS * 1e3, 2),
                "nnz": nnz,
                "iters": ITERS,
            }
        )
    )


def bench_tc():
    import jax
    import numpy as np

    from combblas_tpu.models.tc import triangle_count
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat

    r, c, n = _graph(SCALE, ef=8)
    grid = Grid.make(1, 1)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    t = triangle_count(A)  # warmup/compile (host-orchestrated: sizes once)
    n_tri = int(jax.device_get(t))
    time.sleep(3)
    t0 = time.perf_counter()
    t = triangle_count(A)
    n_tri = int(jax.device_get(t))
    dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "metric": f"tc_rmat_scale{SCALE}_s",
                "value": round(dt, 2),
                "unit": "s",
                "triangles": n_tri,
                "nnz": len(r),
            }
        )
    )


def bench_ppr():
    """W personalized-PageRank chains, one program (pagerank_batch)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from combblas_tpu.models.pagerank import pagerank_batch
    from combblas_tpu.parallel.ellmat import EllParMat
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.vec import DistVec

    W = int(os.environ.get("BENCH_ROOTS", "64"))
    r, c, n = _graph(SCALE)
    grid = Grid.make(1, 1)
    deg = np.bincount(c, minlength=n).astype(np.float32)
    w = (1.0 / np.maximum(deg, 1.0))[c].astype(np.float32)
    E = EllParMat.from_host_coo(grid, r, c, w, n, n)
    dang = DistVec.from_global(
        grid, (deg == 0).astype(np.float32), align="col"
    )
    rng = np.random.default_rng(0)
    srcs = jnp.asarray(
        rng.choice(np.flatnonzero(deg > 0), size=W, replace=False), jnp.int32
    )
    # fixed iteration count (tol=0 -> runs max_iters): clean s/iter
    ranks, it = pagerank_batch(
        E, srcs, dang, tol=0.0, max_iters=ITERS
    )
    jax.block_until_ready(ranks.blocks)
    time.sleep(3)
    t0 = time.perf_counter()
    ranks, it = pagerank_batch(E, srcs, dang, tol=0.0, max_iters=ITERS)
    _ = float(jax.device_get(ranks.blocks[0, 0, 0]))
    dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "metric": f"ppr_batch{W}_rmat_scale{SCALE}_GFLOPs",
                "value": round(len(r) * 2 * W * ITERS / dt / 1e9, 3),
                "unit": "GFLOP/s",
                "nnz": len(r),
                "roots": W,
                "iters": ITERS,
                "ms_per_iter": round(dt / ITERS * 1e3, 2),
                "ms_per_iter_per_root": round(dt / ITERS / W * 1e3, 3),
            }
        )
    )


def bench_tc_fused():
    """TC with host symbolic sizing + ONE fused launch."""
    import jax
    import numpy as np

    from combblas_tpu import PLUS_TIMES
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spgemm import (
        summa_capacities_host,
        summa_spgemm,
        summa_stage_flops_host,
    )
    from combblas_tpu.parallel.spmat import SpParMat

    r, c, n = _graph(SCALE, ef=8)
    grid = Grid.make(1, 1)
    m = r > c  # strict lower triangle, host-side
    lr_, lc_ = r[m], c[m]
    fcap, ocap = summa_capacities_host(grid, lr_, lc_, lr_, lc_, n, n, n)
    ntri_host = None
    L = SpParMat.from_global_coo(
        grid, lr_, lc_, np.ones(len(lr_), np.float32), n, n
    )

    @jax.jit
    def count(Lm):
        B = summa_spgemm(
            PLUS_TIMES, Lm, Lm, flop_capacity=fcap, out_capacity=ocap
        )
        C = B.ewise_mult(Lm)
        return C.reduce(PLUS_TIMES, axis="rows").reduce(PLUS_TIMES)

    t = count(L)
    jax.block_until_ready(t)
    time.sleep(3)
    t0 = time.perf_counter()
    t = count(L)
    n_tri = int(jax.device_get(t))
    dt = time.perf_counter() - t0
    flops = int(
        summa_stage_flops_host(
            grid, lr_, lc_, lr_, lc_, n, n, n, padded=False
        ).sum()
    )
    print(
        json.dumps(
            {
                "metric": f"tc_rmat_scale{SCALE}_s",
                "value": round(dt, 2),
                "unit": "s",
                "triangles": n_tri,
                "nnz": int(len(r)),
                "MFLOPs": round(flops * 2 / dt / 1e6, 2),
            }
        )
    )


def bench_cc(algo: str):
    import jax
    import numpy as np

    from combblas_tpu.models.cc import connected_components, lacc
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat

    r, c, n = _graph(SCALE)
    grid = Grid.make(1, 1)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    fn = lacc if algo == "lacc" else connected_components
    labels, it = fn(A)
    jax.block_until_ready(labels.blocks)
    time.sleep(3)
    t0 = time.perf_counter()
    labels, it = fn(A)
    _ = int(jax.device_get(labels.blocks[0, 0]))
    dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "metric": f"{algo}_rmat_scale{SCALE}_s",
                "value": round(dt, 3),
                "unit": "s",
                "nnz": len(r),
                "iters": int(jax.device_get(it)),
                "MTEPS": round(len(r) * int(jax.device_get(it)) / dt / 1e6, 1),
            }
        )
    )


def bench_sssp():
    import jax
    import numpy as np

    from combblas_tpu.models.sssp import sssp
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat

    r, c, n = _graph(SCALE)
    grid = Grid.make(1, 1)
    rng = np.random.default_rng(0)
    w = (rng.random(len(r)) + 0.01).astype(np.float32)
    A = SpParMat.from_global_coo(grid, r, c, w, n, n)
    dist, it = sssp(A, 0)
    jax.block_until_ready(dist.blocks)
    time.sleep(3)
    t0 = time.perf_counter()
    dist, it = sssp(A, 0)
    _ = float(jax.device_get(dist.blocks[0, 0]))
    dt = time.perf_counter() - t0
    niter = int(jax.device_get(it))
    print(
        json.dumps(
            {
                "metric": f"sssp_rmat_scale{SCALE}_s",
                "value": round(dt, 3),
                "unit": "s",
                "nnz": len(r),
                "iters": niter,
                "MTEPS": round(len(r) * niter / dt / 1e6, 1),
            }
        )
    )


def bench_sssp_batch():
    """W-source Bellman-Ford in one program (the batched ELL kernel)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from combblas_tpu.models.sssp import sssp_batch
    from combblas_tpu.parallel.ellmat import EllParMat
    from combblas_tpu.parallel.grid import Grid

    W = int(os.environ.get("BENCH_ROOTS", "64"))
    r, c, n = _graph(SCALE)
    grid = Grid.make(1, 1)
    rng = np.random.default_rng(0)
    w = (rng.random(len(r)) + 0.01).astype(np.float32)
    E = EllParMat.from_host_coo(grid, r, c, w, n, n)
    deg = np.bincount(r, minlength=n)
    srcs = jnp.asarray(
        rng.choice(np.flatnonzero(deg > 0), size=W, replace=False), jnp.int32
    )
    dist, _, it = sssp_batch(E, srcs)
    jax.block_until_ready(dist.blocks)
    time.sleep(3)
    t0 = time.perf_counter()
    dist, _, it = sssp_batch(E, srcs)
    _ = float(jax.device_get(dist.blocks[0, 0, 0]))
    dt = time.perf_counter() - t0
    niter = int(jax.device_get(it))
    print(
        json.dumps(
            {
                "metric": f"sssp_batch{W}_rmat_scale{SCALE}_s",
                "value": round(dt, 3),
                "unit": "s",
                "nnz": len(r),
                "roots": W,
                "iters": niter,
                "MTEPS_aggregate": round(
                    len(r) * niter * W / dt / 1e6, 1
                ),
            }
        )
    )


def bench_bc():
    import jax
    import numpy as np

    from combblas_tpu.models.bc import bc_batch
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat

    W = int(os.environ.get("BENCH_ROOTS", "16"))
    r, c, n = _graph(SCALE, ef=8)
    grid = Grid.make(1, 1)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    rng = np.random.default_rng(0)
    deg = np.bincount(r, minlength=n)
    srcs = rng.choice(np.flatnonzero(deg > 0), size=W, replace=False)
    AT = A.transpose()
    scores = bc_batch(A, srcs, AT=AT)  # warmup (compiles per-level shapes)
    jax.block_until_ready(scores.blocks)
    time.sleep(3)
    t0 = time.perf_counter()
    scores = bc_batch(A, srcs, AT=AT)
    _ = float(jax.device_get(scores.blocks[0, 0]))
    dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "metric": f"bc_batch{W}_rmat_scale{SCALE}_s",
                "value": round(dt, 2),
                "unit": "s",
                "nnz": len(r),
                "roots": W,
                "note": "host level loop; per-level sizing readbacks "
                        "degrade this chip (D2H poison)",
            }
        )
    )


def bench_bc_dense():
    """One-launch dense batched Brandes (bc_batch_dense) — the TPU-native
    BC: zero readbacks, W sources per program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from combblas_tpu.models.bc import bc_batch_dense
    from combblas_tpu.parallel.ellmat import EllParMat
    from combblas_tpu.parallel.grid import Grid

    W = int(os.environ.get("BENCH_ROOTS", "16"))
    r, c, n = _graph(SCALE, ef=8)
    grid = Grid.make(1, 1)
    E = EllParMat.from_host_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    rng = np.random.default_rng(0)
    deg = np.bincount(r, minlength=n)
    srcs = jnp.asarray(
        rng.choice(np.flatnonzero(deg > 0), size=W, replace=False), jnp.int32
    )
    # static depth bound: R-MAT diameters are tiny; 64 is generous
    scores = bc_batch_dense(E, E, srcs, max_depth=64)
    jax.block_until_ready(scores.blocks)
    time.sleep(3)
    t0 = time.perf_counter()
    scores = bc_batch_dense(E, E, srcs, max_depth=64)
    _ = float(jax.device_get(scores.blocks[0, 0]))
    dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "metric": f"bc_dense{W}_rmat_scale{SCALE}_s",
                "value": round(dt, 2),
                "unit": "s",
                "nnz": len(r),
                "roots": W,
                "s_per_root": round(dt / W, 3),
            }
        )
    )


def bench_mcl():
    """BENCH_ITERS MCL iterations in ONE launch, frozen host-sized caps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from combblas_tpu.models.mcl import (
        _mcl2d_iter_device,
        make_col_stochastic,
    )
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spgemm import summa_capacities_host
    from combblas_tpu.parallel.spmat import SpParMat

    K = ITERS
    r, c, n = _graph(SCALE, ef=8)
    grid = Grid.make(1, 1)
    # self-loops added HOST-side so the symbolic sizing sees the matrix
    # the loop actually squares
    diag = np.arange(n, dtype=np.int64)
    r = np.concatenate([r, diag])
    c = np.concatenate([c, diag])
    fcap, ocap = summa_capacities_host(
        grid, r, c, r, c, n, n, n, slack=2.0
    )
    # Frozen caps must cover LATER iterations too: each squares the
    # previous PRUNED matrix, whose flops are bounded by select^2 * n
    # (<= select entries per column in both operands). BENCH_SELECT
    # trades cluster granularity for a provable capacity bound.
    SELECT = int(os.environ.get("BENCH_SELECT", "64"))
    # CAPX covers the select-bound breaking under VALUE TIES: kselect
    # thresholds keep every tied entry (early MCL iterations tie heavily
    # at 1/deg), so columns can exceed SELECT entries and the flop bound
    # with them (overflow flag in the output = raise CAPX).
    CAPX = int(os.environ.get("BENCH_CAPX", "4"))
    bound = SELECT * SELECT * n
    rnd = lambda x: 1 << (max(int(x), 1) - 1).bit_length()
    caps = (
        rnd(CAPX * max(fcap, bound)),
        # distinct output keys <= min(flop bound, dense)
        min(rnd(min(CAPX * max(ocap, bound), n * n)), n * n),
    )
    prune_kwargs = dict(
        hard_threshold=1e-4, select_num=SELECT,
        recover_num=SELECT + SELECT // 4, recover_pct=0.9,
    )
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n,
    )

    from jax import lax

    @jax.jit
    def block(A0):
        A1 = make_col_stochastic(A0)
        # iteration 1 separately (input capacity differs from ocap)...
        A1, ch, worst = _mcl2d_iter_device(A1, caps, 2.0, prune_kwargs)

        # ...then a fori_loop over the shape-stable remainder (a python
        # unroll of K iterations produced an HLO too large to compile
        # at chip scales in rounds 2-5, on a machine that is gone; not
        # re-measured)
        def body(_, st):
            Ak, _ch, worst = st
            Ak, ch2, ov = _mcl2d_iter_device(Ak, caps, 2.0, prune_kwargs)
            return Ak, ch2, jnp.maximum(worst, ov)

        A1, ch, worst = lax.fori_loop(0, K - 1, body, (A1, ch, worst))
        return A1, ch, worst

    out, ch, worst = block(A)
    jax.block_until_ready(out.vals)
    time.sleep(3)
    t0 = time.perf_counter()
    out, ch, worst = block(A)
    ch_v = float(jax.device_get(ch))
    dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "metric": f"mcl_rmat_scale{SCALE}_s_per_iter",
                "value": round(dt / K, 2),
                "unit": "s/iter",
                "iters": K,
                "nnz": len(r),
                "chaos": round(ch_v, 5),
                "overflow": int(jax.device_get(worst)),
            }
        )
    )


def bench_mcl_dense():
    """Round-4 dense one-launch MCL: the WHOLE clustering loop as one
    lax.while_loop on the MXU (models/mcl.py:dense_mcl_program).

    Protocol: AOT-compile (lower().compile() — no warmup EXECUTION, so no
    pre-timing readback poisons the run), one timed execution closed by
    the iteration-count readback.  No capacities exist in this
    formulation, so overflow is structurally 0; the chaos trajectory is
    carried on device and reported per iteration.
    """
    import jax
    import numpy as np

    from combblas_tpu.models.mcl import dense_mcl_program
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat
    from combblas_tpu.models.mcl import make_col_stochastic

    K = ITERS
    SELECT = int(os.environ.get("BENCH_SELECT", "64"))
    MODE = os.environ.get("BENCH_DENSE_MODE", "bf16x3")
    # EXPLICIT opt-in to plateau detect-and-perturb (the library default
    # is now 0 — kicks can move boundary vertices between clusters, so
    # only the driver turns them on; ADVICE r5). 5e-5 is the round-5
    # operating point; kicks are counted in the artifact.
    PERTURB = float(os.environ.get("BENCH_MCL_PERTURB", "5e-5"))
    r, c, n = _graph(SCALE, ef=8)
    grid = Grid.make(1, 1)
    diag = np.arange(n, dtype=np.int64)
    r = np.concatenate([r, diag])
    c = np.concatenate([c, diag])
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    A = make_col_stochastic(A)
    run = dense_mcl_program(
        n, n, 2.0, 1e-3, K,
        hard=1e-4, select=min(SELECT, n),
        recover=min(SELECT + SELECT // 4, n),
        rpct=0.9, mode=MODE, perturb_delta=PERTURB,
    )
    rows, cols, vals = A.rows[0, 0], A.cols[0, 0], A.vals[0, 0]
    compiled = jax.jit(run).lower(rows, cols, vals).compile()
    time.sleep(2)
    t0 = time.perf_counter()
    m, it, ch, hist, npert = compiled(rows, cols, vals)
    iters = int(jax.device_get(it))  # the closing readback
    dt = time.perf_counter() - t0
    ch_v = float(jax.device_get(ch))
    hist_v = np.asarray(jax.device_get(hist))[:iters]
    kicks = int(jax.device_get(npert))
    from combblas_tpu import obs

    if obs.ENABLED:  # perturbation kicks as span events (ADVICE r5)
        obs.span_event(
            "mcl.perturb", kicks=kicks, delta=PERTURB, iters=iters,
            chaos=round(ch_v, 6),
        )
        obs.count("mcl.perturb_kicks", kicks)
    print(
        json.dumps(
            {
                "metric": f"mcl_dense_rmat_scale{SCALE}_s_per_iter",
                "value": round(dt / max(iters, 1), 3),
                "unit": "s/iter",
                "total_s": round(dt, 3),
                "iters": iters,
                "converged": bool(ch_v < 1e-3),
                "nnz": len(r),
                "chaos": round(ch_v, 6),
                "chaos_trajectory": [round(float(x), 5) for x in hist_v],
                "overflow": 0,
                "perturbations": kicks,
                "perturb_delta": PERTURB,
                "select": SELECT,
                "mode": MODE,
            }
        )
    )


def bench_tc_dense():
    """Round-4 one-launch MXU triangle count (models/tc.py:_tc_dense):
    AOT-compile, one timed execution, readback closes the window."""
    import jax
    import numpy as np

    from combblas_tpu.models.tc import _tc_combine, _tc_dense
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat

    r, c, n = _graph(SCALE, ef=8)
    grid = Grid.make(1, 1)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    rows, cols = A.rows[0, 0], A.cols[0, 0]
    fn = jax.jit(_tc_dense, static_argnums=2)
    compiled = fn.lower(rows, cols, n).compile()
    time.sleep(2)
    t0 = time.perf_counter()
    n_tri = _tc_combine(jax.device_get(compiled(rows, cols)))
    dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "metric": f"tc_dense_rmat_scale{SCALE}_s",
                "value": round(dt, 3),
                "unit": "s",
                "triangles": n_tri,
                "nnz": len(r),
            }
        )
    )


def _enable_cache():
    from combblas_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()


def bench_tc_edgeharvest():
    """Round-5 scale-16 TC: per-edge common-neighbor harvest against the
    dense bf16 adjacency (models/tc.py:_tc_edge_harvest) — the regime
    past the n=32K dense-product ceiling where the ESC sparse path runs
    9.23 MFLOP/s (87 s; VERDICT r4 Missing #2). AOT-compile, one timed
    launch, readback closes the window."""
    _enable_cache()
    import jax
    import numpy as np

    from combblas_tpu.models.tc import (
        _tc_combine,
        _tc_edge_harvest,
        _tc_edge_harvest_bits,
    )
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat

    r, c, n = _graph(SCALE, ef=8)
    grid = Grid.make(1, 1)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    t = A.local_tile(A.rows, A.cols, A.vals, A.nnz)
    chunk = int(os.environ.get("BENCH_TC_CHUNK", "8192"))
    kern = (_tc_edge_harvest_bits
            if os.environ.get("BENCH_TC_BITS", "1") == "1"
            else _tc_edge_harvest)
    fn = jax.jit(kern, static_argnums=(2, 3))
    compiled = fn.lower(t.rows, t.cols, n, chunk).compile()
    time.sleep(3)
    t0 = time.perf_counter()
    hilo = compiled(t.rows, t.cols)
    total3 = _tc_combine(jax.device_get(hilo))  # readback = the barrier
    dt = time.perf_counter() - t0
    tri = total3 // 3
    # sparse-flops equivalence for the standings table: the masked
    # SpGEMM counts 2 ops per multiply over sum_{(i,j) in L} |N(i)| —
    # report the same convention via the wedge count
    print(json.dumps({
        "metric": f"tc_edgeharvest_rmat_scale{SCALE}_s",
        "kernel": kern.__name__,
        "value": round(dt, 3),
        "unit": "s",
        "triangles": tri,
        "nnz": len(r),
        "n": n,
        "traffic_GB": round(
            len(r) * (-(-n // 32) * (4 if kern.__name__.endswith("bits")
                                     else 64)) / 1e9, 1),
        "GBps": round(
            len(r) * (-(-n // 32) * (4 if kern.__name__.endswith("bits")
                                     else 64)) / 1e9 / dt, 1),
    }))


def bench_matching_device():
    """Round-5 chip capture for the ON-DEVICE augmenting matching
    (models/matching.py:maximum_matching_device; VERDICT r4 item 6 +
    Weak #7): each phase's wall time is recorded — phase 1 runs clean,
    phases 2+ run after the phase-1 termination readback, so the
    per-phase times ARE the answer to the D2H-poison question."""
    _enable_cache()
    import jax
    import numpy as np

    from combblas_tpu.models.matching import (
        _mcm_phase,
        maximal_matching,
        ones_f32,
    )
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat

    r, c, n = _graph(SCALE)
    grid = Grid.make(1, 1)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    t_all = time.perf_counter()
    t0 = time.perf_counter()
    mate_row, mate_col = maximal_matching(A)
    jax.block_until_ready(mate_row.blocks)
    init_s = time.perf_counter() - t0
    AT = A.transpose().apply(ones_f32)
    jax.block_until_ready(AT.vals)
    phases = []
    while True:
        t0 = time.perf_counter()
        mate_row, mate_col, n_aug = _mcm_phase(AT, mate_row, mate_col)
        aug = int(n_aug)  # per-phase readback (measured HARMLESS to
        #                     later phases: 0.12-0.15 s each, PERF_NOTES_r5)
        phases.append({"s": round(time.perf_counter() - t0, 3),
                       "augmented": aug})
        if aug == 0:
            break
    total = time.perf_counter() - t_all
    card = int((np.asarray(mate_row.to_global()) >= 0).sum())
    print(json.dumps({
        "metric": f"matching_device_rmat_scale{SCALE}_s",
        "value": round(total, 3),
        "unit": "s",
        "cardinality": card,
        "n": n,
        "nnz": len(r),
        "init_maximal_s": round(init_s, 3),
        "phases": phases,
    }))


def bench_rcm():
    """Round-5 chip capture for RCM ordering (models/ordering.py;
    RCM.cpp:61-160 role). End-to-end wall time including the
    pseudo-peripheral probe (whose per-probe readbacks poison later
    launches on this chip — recorded as-is, like the reference's
    peripheral search is part of its timed driver)."""
    _enable_cache()
    import jax
    import numpy as np

    from combblas_tpu.models.ordering import rcm_ordering
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat

    r, c, n = _graph(SCALE)
    grid = Grid.make(1, 1)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    # warm the kernels with a fixed-root ordering (no peripheral probe)
    p = rcm_ordering(A, root=0)
    jax.block_until_ready(p.blocks)
    time.sleep(3)
    t0 = time.perf_counter()
    p = rcm_ordering(A)
    perm = np.asarray(p.to_global())
    dt = time.perf_counter() - t0
    ok = len(np.unique(perm[perm >= 0])) == n
    print(json.dumps({
        "metric": f"rcm_rmat_scale{SCALE}_s",
        "value": round(dt, 3),
        "unit": "s",
        "n": n,
        "nnz": len(r),
        "is_permutation": bool(ok),
    }))


def bench_awpm():
    """Round-5 chip capture for approximate-weight perfect matching
    (models/matching.py:awpm; the BipartiteMatchings AWPM driver role)."""
    _enable_cache()
    import jax
    import numpy as np

    from combblas_tpu.models.matching import awpm
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat

    r, c, n = _graph(SCALE)
    rng = np.random.default_rng(5)
    w = rng.random(len(r)).astype(np.float32) + 0.1
    grid = Grid.make(1, 1)
    A = SpParMat.from_global_coo(grid, r, c, w, n, n)
    t0 = time.perf_counter()
    mr, mc = awpm(A)
    card = int((np.asarray(mr.to_global()) >= 0).sum())
    dt = time.perf_counter() - t0
    out = {
        "metric": f"awpm_rmat_scale{SCALE}_s",
        "value": round(dt, 3),
        "unit": "s",
        "cardinality": card,
        "n": n,
        "nnz": len(r),
    }
    # matched weight without densifying: sum w over matched (r -> mate)
    mrg = np.asarray(mr.to_global())
    matched = mrg >= 0
    key = r * np.int64(n) + c
    order = np.argsort(key)
    mkey = np.flatnonzero(matched) * np.int64(n) + mrg[matched]
    pos = np.searchsorted(key[order], mkey)
    out["weight"] = round(float(w[order][pos].sum()), 2)
    print(json.dumps(out))


def _obs_setup():
    """BENCH_OBS=1: structured telemetry sidecar for this app process
    (spans + counters -> JSONL; path printed to stderr so the stdout
    JSON-line protocol stays parseable). See docs/observability.md."""
    from combblas_tpu import obs

    return obs.enable_sidecar(APP)


def _obs_finish():
    from combblas_tpu import obs

    if obs.ENABLED:
        # telemetry must never fail the bench: COMBBLAS_OBS=1 enables
        # obs WITHOUT a sidecar path (that's BENCH_OBS=1's job), in
        # which case there is nothing to dump
        try:
            print(f"[obs] {obs.dump_jsonl()}", file=sys.stderr,
                  flush=True)
        except Exception:  # no path configured, unwritable dir, ...
            pass


if __name__ == "__main__":
    _obs_setup()
    if APP == "pagerank":
        bench_pagerank()
    elif APP == "ppr":
        bench_ppr()
    elif APP == "tc":
        bench_tc_fused()
    elif APP in ("cc", "fastsv"):
        bench_cc("fastsv")
    elif APP == "lacc":
        bench_cc("lacc")
    elif APP == "sssp":
        bench_sssp()
    elif APP == "sssp_batch":
        bench_sssp_batch()
    elif APP == "bc":
        bench_bc()
    elif APP == "bc_dense":
        bench_bc_dense()
    elif APP == "mcl":
        bench_mcl()
    elif APP == "mcl_dense":
        bench_mcl_dense()
    elif APP == "tc_edgeharvest":
        bench_tc_edgeharvest()
    elif APP == "matching_device":
        bench_matching_device()
    elif APP == "rcm":
        bench_rcm()
    elif APP == "awpm":
        bench_awpm()
    elif APP == "tc_dense":
        bench_tc_dense()
    else:
        raise SystemExit(f"unknown BENCH_APP {APP}")
    _obs_finish()
