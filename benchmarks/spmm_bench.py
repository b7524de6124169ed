"""Batched SpMM benchmark: fused k-hop sparse×dense vs loop-over-columns
batch SpMV, plus the serve ``"propagate"`` capture.

    python benchmarks/spmm_bench.py          # 8 virtual CPU devices

Three scenarios, one JSON line each plus the official final line (the
``bench.py BENCH_SPMM=1`` wrapper turns it into the standard
``{summary, metric, value, median, warning, rc}`` headline +
``BENCH_SUMMARY.json``):

* **golden** — SpMM agreement on 1x1 AND 2x2 grids against scipy
  ``A @ X`` (plus_times, integer-valued f32 data so f32 accumulation
  is EXACT regardless of fold order) and dense semiring folds
  (min_plus / max_min), duplicate-entry COO included, both backends
  where admissible;
* **perf** (the acceptance gate) — R-MAT scale ``BENCH_SPMM_SCALE``
  (default 14), feature width ``BENCH_SPMM_WIDTH`` (default 64),
  ``BENCH_SPMM_HOPS`` (default 2) hops, on the ``BENCH_SPMM_GRID``
  (default 2x2 — the tier-1 virtual mesh, like the serve bench; the
  lane is a DISTRIBUTED system and the per-launch collective is part
  of what fusion amortizes) mesh:
  BASELINE = loop-over-columns batch SpMV (one warm ``dist_spmv_ell``
  launch per column per hop — what the pre-round-12 stack would do;
  column uploads hoisted out of the timed region, matching the fused
  side's untimed upload);
  FUSED = one ``spmm_khop`` launch.  Gate: fused >= 3x baseline.
  Gold-checked against scipy before timing.  Reference points on this
  box: 4.9x on the 2x2 mesh, 2.5x on 1x1 (``BENCH_SPMM_GRID=1x1`` —
  no collectives, so only launch overhead and payload vectorization
  amortize; the TPU gather's free payload width is absent on CPU).
* **serve** — a ``"propagate"`` engine (features loaded, warm lanes),
  ``BENCH_SPMM_QUERIES`` (default 128) single-root queries through the
  batched ``Server``; gates on ZERO post-warmup retraces and reports
  queries/s + p50/p99 latency.

``ok`` in the final line is the AND of the gates (golden, >=3x, zero
retraces).
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
)

SCALE = int(os.environ.get("BENCH_SPMM_SCALE", "14"))
EDGEFACTOR = int(os.environ.get("BENCH_SPMM_EDGEFACTOR", "8"))
FEATW = int(os.environ.get("BENCH_SPMM_WIDTH", "64"))
HOPS = int(os.environ.get("BENCH_SPMM_HOPS", "2"))
NQUERIES = int(os.environ.get("BENCH_SPMM_QUERIES", "128"))
REPEATS = int(os.environ.get("BENCH_SPMM_REPEATS", "3"))
GRID = os.environ.get("BENCH_SPMM_GRID", "2x2")


def _percentile(xs, q):
    # the shared obs quantile helper (round 15): one percentile
    # implementation for benches, the registry, and the exporter
    from combblas_tpu.obs.sinks import quantiles

    return quantiles(xs, (q,))[q]


def _rmat(scale, edgefactor, seed=7):
    import jax
    import numpy as np

    from combblas_tpu.utils.rmat import rmat_symmetric_coo

    rows, cols = rmat_symmetric_coo(
        jax.random.key(seed), scale=scale, edgefactor=edgefactor
    )
    return np.asarray(rows), np.asarray(cols)


def run_golden():
    """Exact agreement, small scale, 1x1 + 2x2 grids, dup COO."""
    import numpy as np

    from combblas_tpu.parallel.ellmat import EllParMat
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.vec import DistMultiVec
    from combblas_tpu.parallel.spmm import dist_spmm_ell
    from combblas_tpu.semiring import MAX_MIN, MIN_PLUS, PLUS_TIMES

    rng = np.random.default_rng(0)
    n, m, F = 256, 1500, 24
    r = rng.integers(0, n, m)
    c = rng.integers(0, n, m)
    r = np.concatenate([r, r[:100]])  # duplicates on purpose
    c = np.concatenate([c, c[:100]])
    v = rng.integers(1, 5, len(r)).astype(np.float32)
    X = rng.integers(0, 4, (n, F)).astype(np.float32)
    A = np.zeros((n, n), np.float32)
    np.add.at(A, (r, c), v)

    def golden(name):
        if name == "plus_times":
            return A @ X
        big = np.full(
            (n, F), np.inf if name == "min_plus" else -np.inf, np.float32
        )
        for rr, cc, vv in zip(r, c, v):
            if name == "min_plus":
                big[rr] = np.minimum(big[rr], vv + X[cc])
            else:
                big[rr] = np.maximum(big[rr], np.minimum(vv, X[cc]))
        return big

    checks = 0
    for grid in (Grid.make(1, 1), Grid.make(2, 2)):
        E = EllParMat.from_host_coo(grid, r, c, v, n, n)
        Xd = DistMultiVec.from_global(grid, X, align="col")
        for sr in (PLUS_TIMES, MIN_PLUS, MAX_MIN):
            g = golden(sr.name)
            backends = (
                ("mxu_gather", "scatter")
                if sr.name == "plus_times" else ("scatter",)
            )
            for backend in backends:
                got = dist_spmm_ell(sr, E, Xd, backend=backend).to_global()
                if not np.allclose(got, g, equal_nan=True):
                    return {"golden_ok": False, "checks": checks,
                            "failed": f"{grid.pr}x{grid.pc}/"
                                      f"{sr.name}/{backend}"}
                checks += 1
    return {"golden_ok": True, "checks": checks}


def run_perf():
    """The >=3x gate: fused k-hop SpMM vs loop-over-columns SpMV."""
    import jax
    import numpy as np

    from combblas_tpu.parallel.ellmat import (
        EllParMat, dist_spmv_ell,
    )
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.vec import DistMultiVec, DistVec
    from combblas_tpu.parallel.spmm import (
        _spmm_khop_impl, pad_features, spmm_backend_heuristic,
    )
    from combblas_tpu.semiring import PLUS_TIMES

    rows, cols = _rmat(SCALE, EDGEFACTOR)
    n = 1 << SCALE
    rng = np.random.default_rng(3)
    # integer-valued f32: k-hop plus_times sums stay exactly
    # representable, so the scipy golden is EXACT (==)
    X = rng.integers(0, 3, (n, FEATW)).astype(np.float32)
    pr, pc = (int(x) for x in GRID.split("x"))
    grid = Grid.make(pr, pc)
    ones = np.ones(len(rows), np.float32)
    t0 = time.perf_counter()
    E = EllParMat.from_host_coo(grid, rows, cols, ones, n, n)
    build_s = time.perf_counter() - t0
    backend = spmm_backend_heuristic(PLUS_TIMES)

    # golden (scipy CSR) before timing
    try:
        import scipy.sparse as sp

        A = sp.csr_matrix(
            (ones, (rows, cols)), shape=(n, n), dtype=np.float32
        )
        G = X
        for _ in range(HOPS):
            G = A @ G
        golden_available = True
    except ImportError:
        golden_available = False

    Xd = DistMultiVec.from_global(grid, pad_features(X), align="col")
    fused = _spmm_khop_impl(
        PLUS_TIMES, E, Xd, None, HOPS, backend, False
    )
    jax.block_until_ready(fused.blocks)
    got = fused.to_global()[:, :FEATW]
    # None = "scipy unavailable, exactness unchecked" — reported as a
    # skip, NOT folded into the acceptance verdict as a failure (an
    # absent optional dep must not masquerade as a numerical bug)
    golden_exact = (
        bool(np.array_equal(got, G)) if golden_available else None
    )

    # baseline: one column at a time, k chained SpMV launches each.
    # Columns are uploaded ONCE, outside the timed region (the fused
    # path's Xd upload is also untimed) — the gate isolates the
    # launch-count / fusion effect, not host-transfer overhead.
    cols_dev = [
        DistVec.from_global(grid, X[:, f].copy(), align="col")
        for f in range(FEATW)
    ]
    y = dist_spmv_ell(PLUS_TIMES, E, cols_dev[0])  # warm the one shape
    jax.block_until_ready(y.blocks)

    def run_baseline():
        outs = []
        for v in cols_dev:
            for _ in range(HOPS):
                v = dist_spmv_ell(PLUS_TIMES, E, v)
            outs.append(v.blocks)
        jax.block_until_ready(outs)

    def run_fused():
        out = _spmm_khop_impl(
            PLUS_TIMES, E, Xd, None, HOPS, backend, False
        )
        jax.block_until_ready(out.blocks)

    base_ts, fused_ts = [], []
    for _ in range(max(REPEATS, 1)):
        t0 = time.perf_counter()
        run_baseline()
        base_ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_fused()
        fused_ts.append(time.perf_counter() - t0)
    base_s = sorted(base_ts)[len(base_ts) // 2]
    fused_s = sorted(fused_ts)[len(fused_ts) // 2]
    speedup = base_s / fused_s if fused_s > 0 else 0.0
    return {
        "scale": SCALE, "edgefactor": EDGEFACTOR, "feature_width": FEATW,
        "hops": HOPS, "grid": GRID, "nnz": int(len(rows)), "backend": backend,
        "build_s": round(build_s, 3),
        "baseline_loop_spmv_s": round(base_s, 4),
        "fused_spmm_s": round(fused_s, 4),
        "speedup": round(speedup, 2),
        "speedup_ok": bool(speedup >= 3.0),
        "golden_exact": golden_exact,
        "repeats": {"baseline": [round(t, 4) for t in base_ts],
                    "fused": [round(t, 4) for t in fused_ts]},
    }


def run_serve():
    """The ``"propagate"`` serve capture: warm lanes, zero retraces."""
    import numpy as np

    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import GraphEngine
    from combblas_tpu.serve.scheduler import ServeConfig

    scale = int(os.environ.get("BENCH_SPMM_SERVE_SCALE", "11"))
    width = int(os.environ.get("BENCH_SPMM_SERVE_WIDTH", "16"))
    n = 1 << scale
    rows, cols = _rmat(scale, EDGEFACTOR, seed=11)
    rng = np.random.default_rng(5)
    X = rng.random((n, FEATW)).astype(np.float32)
    grid = Grid.make(2, 2)
    t0 = time.perf_counter()
    engine = GraphEngine.from_coo(
        grid, rows, cols, n, features=X,
        propagate_hops=HOPS, propagate_normalize=True,
        kinds=("bfs", "propagate"),
    )
    load_s = time.perf_counter() - t0
    cfg = ServeConfig(lane_widths=(1, 4, width), max_wait_s=0.002)
    lat = []
    with engine.serve(cfg) as srv:
        t0 = time.perf_counter()
        srv.warmup()
        warmup_s = time.perf_counter() - t0
        mark = engine.trace_mark()
        roots = rng.integers(0, n, NQUERIES)
        t0 = time.perf_counter()
        futs = []
        for r in roots:
            ts = time.perf_counter()
            futs.append((ts, srv.submit("propagate", int(r))))
        for ts, f in futs:
            feats = f.result(timeout=120)["features"]
            assert feats.shape == (FEATW,), feats.shape
            lat.append(time.perf_counter() - ts)
        total_s = time.perf_counter() - t0
        retraces = engine.retraces_since(mark)
        stats = srv.stats()
    return {
        "serve_scale": scale, "serve_width": width,
        "queries": NQUERIES,
        "queries_per_s": round(NQUERIES / total_s, 1),
        "p50_ms": round(1e3 * _percentile(lat, 0.50), 2),
        "p99_ms": round(1e3 * _percentile(lat, 0.99), 2),
        "retraces_after_warmup": int(retraces),
        "zero_retrace_ok": bool(retraces == 0),
        "load_s": round(load_s, 2), "warmup_s": round(warmup_s, 2),
        "batches": stats["batches"],
    }


def main():
    from combblas_tpu.utils import device_fields

    dev = device_fields()
    out = {"metric": "spmm_khop_speedup", "unit": "x", **dev}
    golden = run_golden()
    print(json.dumps({"phase": "golden", **golden, **dev}), flush=True)
    perf = run_perf()
    print(json.dumps({"phase": "perf", **perf, **dev}), flush=True)
    serve = run_serve()
    print(json.dumps({"phase": "serve", **serve, **dev}), flush=True)
    out.update(
        value=perf["speedup"],
        golden=golden, perf=perf, serve=serve,
        ok=bool(
            golden.get("golden_ok")
            and perf.get("speedup_ok")
            # None (scipy absent) skips the exactness gate visibly
            # rather than failing it; False stays a hard failure
            and perf.get("golden_exact") is not False
            and serve.get("zero_retrace_ok")
        ),
    )
    if perf.get("golden_exact") is None:
        out["warning"] = "scipy unavailable — perf exactness gate skipped"
    if not out["ok"]:
        out["warning"] = "a gate failed (golden / >=3x / retraces)"
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
