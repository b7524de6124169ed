#!/usr/bin/env python3
"""chip_smoke.py — the served path and the Graph500 BFS kernels, on the chip.

    python chip_smoke.py            # serve1 + kernels (+ mesh4 on >= 4 chips)
    python chip_smoke.py --fleet    # 2 process replicas, one chip each

The quickest proof that the system still starts on the accelerator: it
drives the main path once through the entry points a user calls
(``GraphEngine.from_coo`` -> ``engine.serve`` -> ``Server.submit`` /
``submit_update``) on a Graph500 kernel-2 graph (R-MAT A=.57 B=C=.19,
edgefactor 16, scale 20: n = 1,048,576, ~31M directed nonzeros), runs the
benchmark's BFS kernels on the same graph, and checks every answer
against a plain scipy/numpy reference that shares no code with the
package.  The checks run outside every timed span.

Contract (the driver's, and ISSUE 21's):

* no arguments, fixed sizes; ``--scale`` / ``--seed`` are for development;
* it runs ONLY on a TPU: each phase's first act is to require
  ``jax.default_backend() == "tpu"``.  It never sets ``JAX_PLATFORMS``
  and has no CPU mode; with no accelerator, or with nothing of the repo
  beside it, it exits non-zero and prints no result;
* a chip belongs to one process at a time: this parent imports no JAX,
  and each phase is a fresh child ``python chip_smoke.py --phase NAME``
  run one after the other (so the second phase's compiles also test the
  persistent cache across processes);
* no ``try/except`` turns a failed phase into a pass: a wrong answer, a
  failed future, a retrace after warm-up or a compile error exits
  non-zero;
* stdout is two JSON lines.  The LAST is the verdict and nothing else:
  ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
  with the device as JAX reported it.  The line before it is the summary:
  per phase the walls it observed (set-up, compile, per-kind query, the
  W=256 batch), ending ``"claim": null``.  Observations, not metrics.

Phases:

``serve1``  one chip, ``Grid.make(1, 1)``, kinds bfs/sssp/pagerank, lane
            widths (1, 4, 16): warm-up, 16 concurrent BFS, 4 SSSP, 4
            personalised PageRank, ``bfs_batch_compact`` at W=256 and
            ``bfs_single`` on the same graph, one acknowledged insert
            read back by a later BFS, a second BFS wave; zero retraces
            after warm-up, no failed or retried batch.
``kernels`` the kernels no test compiles without ``interpret``: the Pallas
            tropical matmul at its callers' block sizes, the TPU branch
            of ``sparsify_windowed``, one ``spgemm_auto`` on the ``dot``
            backend the TPU selects — each against numpy/scipy.
``mesh4``   (>= 4 chips) one process, ``Grid.make(2, 2)``, scale 22 (the
            per-chip nonzeros of the one-chip run): 16 concurrent BFS,
            exact levels, shards of every operand and result on four
            distinct devices.
``fleet``   (``--fleet`` only) a seed child saves a version and its own
            BFS answer; then a router that holds no chip boots a
            2-replica ``ProcessFleet.from_checkpoint``, one chip per
            replica, and gets the same answer from each.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

SCALE_1CHIP = 20
SCALE_4CHIP = 22   # per-chip nonzeros equal the one-chip scale-20 run
EDGEFACTOR = 16
SEED = 1
BATCH_W = 256      # the k2-batch cell's batch width
REF_ROOTS = 4      # roots checked against the reference
LANE_WIDTHS = (1, 4, 16)
RESULT_TIMEOUT_S = 600.0
PHASE_TIMEOUT_S = 1100.0


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def check(cond, what: str) -> None:
    """A failed check fails the phase (and so the run)."""
    if not cond:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {what}")


# --------------------------------------------------------------------------
# the plain reference (numpy / scipy only — nothing from the package)
# --------------------------------------------------------------------------


def edge_weights(rows, cols, seed: int):
    """Seeded weights in (0, 1], symmetric in (i, j), multiples of 1/256
    (path sums are then exact in f32 and f64 alike)."""
    import numpy as np

    lo = np.minimum(rows, cols).astype(np.uint64)
    hi = np.maximum(rows, cols).astype(np.uint64)
    h = lo * np.uint64(0x9E3779B97F4A7C15) + hi * np.uint64(
        0xC2B2AE3D27D4EB4F
    ) + np.uint64(seed)
    h ^= h >> np.uint64(29)
    h *= np.uint64(0xBF58476D1CE4E5B9)
    h ^= h >> np.uint64(32)
    return ((h % np.uint64(255)) + np.uint64(1)).astype(np.float32) / 256.0


def ref_graph(n: int, rows, cols, w):
    """scipy digraph of the COO.  A package entry (r, c) is the edge
    c -> r; csgraph reads G[i, j] as i -> j — hence the swap."""
    import scipy.sparse as sp

    return sp.csr_matrix((w, (cols, rows)), shape=(n, n))


def ref_bfs_levels(G, root: int):
    """Hop counts from ``root`` (-1 unreachable), scipy dijkstra with
    every edge counted as 1."""
    import numpy as np
    from scipy.sparse import csgraph

    d = csgraph.dijkstra(G, indices=int(root), unweighted=True)
    return np.where(np.isfinite(d), d, -1).astype(np.int32)


def ref_pagerank(n: int, rows, cols, sources, alpha: float, tol: float,
                 max_iters: int):
    """Personalised PageRank by numpy power iteration: column-stochastic
    P (entry (r, c) = 1/outdeg(c)), dangling mass and the teleport both
    go to the lane's own source; stop at L1 change <= tol."""
    import numpy as np
    import scipy.sparse as sp

    outdeg = np.bincount(cols, minlength=n).astype(np.float64)
    P = sp.csr_matrix(
        (1.0 / outdeg[cols], (rows, cols)), shape=(n, n)
    )
    dangling = outdeg == 0
    out = []
    for s in sources:
        e = np.zeros(n)
        e[int(s)] = 1.0
        x = e.copy()
        for _ in range(max_iters):
            nx = alpha * (P @ x + x[dangling].sum() * e) + (1 - alpha) * e
            err = np.abs(nx - x).sum()
            x = nx
            if err <= tol:
                break
        out.append(x)
    return out


def check_tree(levels, parents, root: int, edge_keys, n: int, what: str):
    """Graph500 tree rule against reference ``levels``: the root is its
    own parent, every reached vertex's parent sits one level up and
    (parent, v) is an edge; unreached vertices have no parent."""
    import numpy as np

    parents = np.asarray(parents).astype(np.int64)
    reached = levels >= 0
    check(int(parents[root]) == root, f"{what}: root is its own parent")
    check(bool(np.all(parents[~reached] < 0)),
          f"{what}: unreached vertices have no parent")
    v = np.flatnonzero(reached)
    v = v[v != root]
    p = parents[v]
    check(bool(np.all(p >= 0)), f"{what}: reached vertices have a parent")
    check(bool(np.all(levels[p] == levels[v] - 1)),
          f"{what}: level[parent[v]] == level[v] - 1")
    key = v * np.int64(n) + p  # entry (v, p) is the edge p -> v
    pos = np.searchsorted(edge_keys, key)
    pos = np.minimum(pos, len(edge_keys) - 1)
    check(bool(np.all(edge_keys[pos] == key)),
          f"{what}: (parent[v], v) is an edge")


# --------------------------------------------------------------------------
# shared set-up
# --------------------------------------------------------------------------


def require_tpu() -> dict:
    """The backend gate: start the backend, require a TPU, name it."""
    import jax
    import jaxlib

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: backend is {backend!r}, not 'tpu' "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '<unset>')}); "
            "this script runs only on the chip"
        )
    from importlib.metadata import version

    from combblas_tpu.utils import device_fields

    dev = dict(device_fields(), jax=jax.__version__,
               jaxlib=jaxlib.__version__, libtpu=version("libtpu"))
    log(f"backend: {dev}")
    log("devices: " + ", ".join(
        f"{d.id}@{getattr(d, 'coords', None)}" for d in jax.devices()
    ))
    return dev


def start_phase():
    """Gate, then telemetry + the persistent compile cache (process
    start is where a program turns it on; libraries never do)."""
    dev = require_tpu()
    from combblas_tpu import obs
    from combblas_tpu.utils import compile_cache

    obs.enable()
    compile_cache.enable_compile_cache()
    dev["cache_dir"] = compile_cache.configured_dir()
    return dev


def cache_counts() -> tuple[int, int]:
    from combblas_tpu import obs

    return (
        int(obs.registry.get_counter("compile_cache.hits")),
        int(obs.registry.get_counter("compile_cache.misses")),
    )


def build_graph(scale: int, seed: int):
    """Graph500 kernel-2 input: seeded R-MAT, symmetrised, de-looped,
    deduplicated; plus symmetric seeded weights.  Host only."""
    import numpy as np

    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    n = 1 << scale
    rows, cols = rmat_symmetric_coo_host(seed, scale, EDGEFACTOR)
    key = np.unique(rows * np.int64(n) + cols)
    rows = (key // n).astype(np.int32)
    cols = (key % n).astype(np.int32)
    return n, rows, cols, edge_weights(rows, cols, seed), key


def pick_roots(rows, n: int, seed: int, count: int):
    import numpy as np

    deg = np.bincount(rows, minlength=n)
    rng = np.random.default_rng(seed)
    return deg, rng.choice(
        np.flatnonzero(deg > 0), size=count, replace=False
    ).astype(np.int32)


def load_and_warm(grid, rows, cols, n: int, lane_widths, out: dict,
                  **from_coo_kw):
    """Load through the user entry points and warm every served plan:
    ``GraphEngine.from_coo`` (host ELL bucketing + upload — ``upload_s``),
    ``engine.serve``, ``srv.warmup()`` (``compile_warmup_s`` with the
    persistent-cache hits/misses of exactly that window), ``srv.start()``.
    Returns (engine, server, trace mark)."""
    import jax

    from combblas_tpu.serve import GraphEngine, ServeConfig

    t0 = time.perf_counter()
    engine = GraphEngine.from_coo(grid, rows, cols, n, **from_coo_kw)
    jax.block_until_ready([a for b in engine.E.buckets for a in b])
    out["upload_s"] = round(time.perf_counter() - t0, 2)
    srv = engine.serve(ServeConfig(lane_widths=lane_widths))
    h0, m0 = cache_counts()
    t0 = time.perf_counter()
    srv.warmup()
    out["compile_warmup_s"] = round(time.perf_counter() - t0, 2)
    h1, m1 = cache_counts()
    out.update(cache_hits=h1 - h0, cache_misses=m1 - m0)
    log(f"loaded: upload_s={out['upload_s']}; warm-up "
        f"{out['compile_warmup_s']} s, persistent cache "
        f"{out['cache_hits']} hits / {out['cache_misses']} misses")
    srv.start()
    return engine, srv, engine.trace_mark()


def results(futs):
    return [f.result(timeout=RESULT_TIMEOUT_S) for f in futs]


def check_server_clean(srv, engine, mark: int) -> None:
    st = srv.stats()
    check(engine.retraces_since(mark) == 0,
          f"zero retraces after warm-up (got {engine.retraces_since(mark)})")
    check(st["retry_batches"] == 0 and st["worker_errors"] == 0,
          f"no failed or retried batch ({st['retry_batches']} retry "
          f"batches, {st['worker_errors']} worker errors)")
    for kind, pk in st["per_kind"].items():
        bad = {k: pk[k] for k in ("poisoned", "retried", "timeout",
                                  "rejected", "invalid") if pk[k]}
        check(not bad, f"no failed {kind} request ({bad})")
    check(st["updates"]["failed"] == 0, "no failed merge")


# --------------------------------------------------------------------------
# phase serve1 — one chip
# --------------------------------------------------------------------------


def phase_serve1(scale: int, seed: int) -> dict:
    dev = start_phase()
    import jax
    import numpy as np

    from combblas_tpu.models.bfs import (
        DEFAULT_SEQ_TIERS, batch_traversed_edges, bfs_batch_compact,
        bfs_single, parse_tier_spec,
    )
    from combblas_tpu.parallel.ellmat import build_csc_companion
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.vec import DistVec

    # kernel 3's plain reference is the benchmark's (numpy / scipy only)
    from chipbench.k3ref import K3Reference

    out = dict(dev, scale=scale)
    t0 = time.perf_counter()
    n, rows, cols, w, keys = build_graph(scale, seed)
    deg, roots = pick_roots(rows, n, seed, BATCH_W)
    out.update(nnz=int(len(rows)), build_s=round(time.perf_counter() - t0, 2))
    log(f"graph: n={n} nnz={len(rows)} build_s={out['build_s']}")
    ref_roots = [int(r) for r in roots[:REF_ROOTS]]

    grid = Grid.make(1, 1)
    engine, srv, mark = load_and_warm(
        grid, rows, cols, n, LANE_WIDTHS, out, weights=w,
        kinds=("bfs", "sssp", "pagerank"), keep_coo=True,
    )

    # -- reads: 16 concurrent BFS (the width-16 lane), 4 SSSP, 4 PageRank
    query_s = {}
    t0 = time.perf_counter()
    bfs1 = results([srv.submit("bfs", int(r)) for r in roots[:16]])
    query_s["bfs"] = round(time.perf_counter() - t0, 3)
    out["bfs_exec_first_ms"] = round(1e3 * query_s["bfs"], 1)
    t0 = time.perf_counter()
    sssp = results([srv.submit("sssp", r) for r in ref_roots])
    query_s["sssp"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    prank = results([srv.submit("pagerank", r) for r in ref_roots])
    query_s["pagerank"] = round(time.perf_counter() - t0, 3)
    out["query_s"] = query_s
    log(f"queries: {query_s}")
    check(engine.stats()["plans"]["bfs/16"]["executions"] >= 1,
          "the width-16 BFS lane executed")
    check_server_clean(srv, engine, mark)

    # -- the benchmark's kernels on the same graph (before the write: the
    # single-root kernel reuses the CSC companion as its CSR, which needs
    # the graph symmetric)
    E = engine.E
    deg_blocks = DistVec.from_global(grid, deg.astype(np.int32),
                                     align="row").blocks
    coldeg_blocks = DistVec.from_global(grid, deg.astype(np.int32),
                                        align="col").blocks
    roots_dev = jax.device_put(roots)
    t0 = time.perf_counter()
    p, _, _ = bfs_batch_compact(E, roots_dev)
    jax.block_until_ready(batch_traversed_edges(deg_blocks, p))
    out["batch256_compile_s"] = round(time.perf_counter() - t0, 2)
    del p
    t0 = time.perf_counter()
    bp, bl, _ = bfs_batch_compact(E, roots_dev)
    te = np.asarray(batch_traversed_edges(deg_blocks, bp))  # the barrier
    out["batch256_s"] = round(time.perf_counter() - t0, 3)
    out["batch256_te"] = int(te.astype(np.int64).sum())
    log(f"bfs_batch_compact W={BATCH_W}: first call "
        f"{out['batch256_compile_s']} s, then {out['batch256_s']} s")
    batch_levels = np.asarray(bl.blocks[0, :n, :REF_ROOTS]).astype(np.int32)
    batch_parents = np.asarray(bp.blocks[0, :n, :REF_ROOTS])
    del bp, bl

    csc = build_csc_companion(grid, rows, cols, n, n)
    tiers = parse_tier_spec(DEFAULT_SEQ_TIERS)
    single = lambda: bfs_single(
        E, ref_roots[0], csc, csr=csc, tiers=tiers,
        coldeg=coldeg_blocks, rowdeg=deg_blocks,
    )
    t0 = time.perf_counter()
    jax.block_until_ready(single()[0].blocks)
    out["single_compile_s"] = round(time.perf_counter() - t0, 2)
    t0 = time.perf_counter()
    sp_, sl_, _ = single()
    single_levels = np.asarray(sl_.blocks[0, :n])
    single_parents = np.asarray(sp_.blocks[0, :n])
    out["single_s"] = round(time.perf_counter() - t0, 3)
    log(f"bfs_single: first call {out['single_compile_s']} s, "
        f"then {out['single_s']} s")
    del csc

    # -- one write, acknowledged, then read back.  Endpoint a has degree
    # 5: one more entry stays inside its ELL width class (6), so the
    # merge is incremental and keeps every operand shape (docs/dynamic.md)
    rng = np.random.default_rng(seed + 1)
    a = int(rng.choice(np.flatnonzero(deg == 5)))
    absent = lambda k: keys[min(np.searchsorted(keys, k), len(keys) - 1)] != k
    b = next(
        int(r) for r in roots[16:]
        if int(r) != a and absent(np.int64(a) * n + int(r))
    )
    t0 = time.perf_counter()
    ack = srv.submit_update([("insert", a, b)]).result(
        timeout=RESULT_TIMEOUT_S
    )
    out["update_s"] = round(time.perf_counter() - t0, 2)
    out["update_mode"] = ack["mode"]
    log(f"insert ({a}, {b}) acknowledged: {ack}")
    after = srv.submit("bfs", b).result(timeout=RESULT_TIMEOUT_S)
    t0 = time.perf_counter()
    bfs2 = results([srv.submit("bfs", int(r)) for r in roots[:16]])
    out["bfs_exec_last_ms"] = round(1e3 * (time.perf_counter() - t0), 1)
    check_server_clean(srv, engine, mark)
    srv.close()

    # -- checks against the reference, outside every timed span
    t0 = time.perf_counter()
    G = ref_graph(n, rows, cols, w)
    alpha, tol, iters = engine.pagerank_opts
    pr_ref = ref_pagerank(n, rows, cols, ref_roots, alpha, tol, iters)
    rows2 = np.append(rows, np.int32(a))
    cols2 = np.append(cols, np.int32(b))
    G2 = ref_graph(n, rows2, cols2, np.append(w, np.float32(1.0)))
    keys2 = np.sort(np.append(keys, np.int64(a) * n + b))
    ref_levels = [ref_bfs_levels(G, r) for r in ref_roots]
    k3 = K3Reference(n, rows, cols, w)
    for i, (r, lv) in enumerate(zip(ref_roots, ref_levels)):
        check(np.array_equal(bfs1[i]["levels"], lv),
              f"served BFS levels exact, root {r}")
        check_tree(lv, bfs1[i]["parents"], r, keys, n, f"served BFS root {r}")
        check(np.array_equal(batch_levels[:, i], lv),
              f"bfs_batch_compact levels exact, root {r}")
        check_tree(lv, batch_parents[:, i], r, keys, n,
                   f"bfs_batch_compact root {r}")
        check(int(te[i]) == int(deg[lv >= 0].sum()) // 2,
              f"batch_traversed_edges, root {r}")
        bad = k3.check_exact(sssp[i]["dist"], r) or k3.check_tree(
            sssp[i]["dist"], sssp[i]["parents"], r)
        check(bad is None, "served SSSP: exact distances and Graph500 "
                           f"kernel 3's five rules, root {r} ({bad})")
        got = np.asarray(prank[i]["ranks"], np.float64)
        check(bool(np.all(np.isfinite(got))) and abs(got.sum() - 1) < 1e-3,
              f"PageRank lane sums to 1, root {r}")
        l1 = float(np.abs(got - pr_ref[i]).sum())
        # both sides stop within tol of the fixed point's contraction;
        # f32 accumulation over n entries is the rest
        check(l1 < 1e-4, f"PageRank L1 error {l1:.2e} < 1e-4, root {r}")
        lv2 = ref_bfs_levels(G2, r)
        check(np.array_equal(bfs2[i]["levels"], lv2),
              f"second-wave BFS levels exact on the updated graph, root {r}")
        check_tree(lv2, bfs2[i]["parents"], r, keys2, n,
                   f"second-wave BFS root {r}")
    check(np.array_equal(single_levels, ref_levels[0]),
          "bfs_single levels exact")
    check_tree(ref_levels[0], single_parents, ref_roots[0], keys, n,
               "bfs_single")
    lvb = ref_bfs_levels(G2, b)
    check(int(lvb[a]) == 1, "reference: the new edge puts a at level 1")
    check(np.array_equal(after["levels"], lvb),
          "BFS after the acknowledged insert sees the updated graph")
    check(int(after["levels"][a]) == 1 and int(after["parents"][a]) == b,
          "BFS after the acknowledged insert traverses the new edge")
    check(ack["mode"] == "incremental",
          f"in-class insert merged incrementally (mode={ack['mode']})")
    out["check_s"] = round(time.perf_counter() - t0, 2)
    ht, mt = cache_counts()
    out.update(cache_hits_total=ht, cache_misses_total=mt)
    return out


# --------------------------------------------------------------------------
# phase kernels — compile, without interpret, once each
# --------------------------------------------------------------------------


def kernel_min_plus() -> None:
    """The Pallas tropical matmul at the block sizes its two callers
    pass (``parallel/spgemm.py``: 256/512/256, f32)."""
    import jax.numpy as jnp
    import numpy as np

    from combblas_tpu.ops.pallas_kernels import semiring_matmul

    rng = np.random.default_rng(SEED)
    a = rng.integers(0, 64, (512, 1024)).astype(np.float32)
    b = rng.integers(0, 64, (1024, 512)).astype(np.float32)
    got = np.asarray(semiring_matmul(
        "min_plus", jnp.asarray(a), jnp.asarray(b), bm=256, bk=512, bn=256
    ))
    want = np.full((512, 512), np.inf, np.float32)
    for k0 in range(0, 1024, 64):
        want = np.minimum(
            want,
            (a[:, k0:k0 + 64, None] + b[None, k0:k0 + 64, :]).min(axis=1),
        )
    check(np.array_equal(got, want), "Pallas min_plus matmul 256/512/256")


def kernel_sparsify() -> None:
    """The ``default_backend() == "tpu"`` branch of ``sparsify_windowed``
    (group counts by one bf16 MXU matmul)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from combblas_tpu.ops.spgemm import sparsify_windowed

    rng = np.random.default_rng(SEED)
    dense = np.where(rng.random((1024, 1024)) < 0.01,
                     rng.integers(1, 9, (1024, 1024)), 0).astype(np.float32)
    tup, total = jax.jit(
        lambda d: sparsify_windowed(d, 0.0, 1000, 1000, 1 << 14)
    )(jnp.asarray(dense))
    nnz = int(total)
    wr, wc = np.nonzero(dense[:1000, :1000])
    check(nnz == len(wr), f"sparsify_windowed count {nnz} == {len(wr)}")
    check(np.array_equal(np.asarray(tup.rows)[:nnz], wr)
          and np.array_equal(np.asarray(tup.cols)[:nnz], wc)
          and np.array_equal(np.asarray(tup.vals)[:nnz],
                             dense[:1000, :1000][wr, wc]),
          "sparsify_windowed entries (row-major, exact)")


def kernel_spgemm_auto() -> None:
    """``spgemm_auto`` on the accumulate backend the platform selects
    (``dot`` on a TPU), against scipy ``A @ A`` at scale 12."""
    import numpy as np
    import scipy.sparse as sp

    from combblas_tpu import PLUS_TIMES, SpParMat, spgemm_auto
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spgemm import resolve_spgemm_backend

    check(resolve_spgemm_backend() == "dot",
          "the TPU selects the 'dot' accumulate backend")
    n, rows, cols, _, _ = build_graph(12, SEED)
    A = SpParMat.from_global_coo(
        Grid.make(1, 1), rows, cols, np.ones(len(rows), np.float32), n, n
    )
    cr, cc, cv = spgemm_auto(PLUS_TIMES, A, A).to_global_coo()
    S = sp.csr_matrix((np.ones(len(rows)), (rows, cols)), shape=(n, n))
    want = (S @ S).tocsr()
    got = sp.coo_matrix((cv, (cr, cc)), shape=(n, n)).tocsr()
    check(got.nnz == want.nnz and (got != want).nnz == 0,
          f"spgemm_auto(A, A) == scipy A @ A at scale 12 ({want.nnz} nnz)")


def phase_kernels() -> dict:
    out = start_phase()
    for name, fn in (("min_plus_s", kernel_min_plus),
                     ("sparsify_s", kernel_sparsify),
                     ("spgemm_auto_s", kernel_spgemm_auto)):
        t0 = time.perf_counter()
        fn()
        out[name] = round(time.perf_counter() - t0, 2)
        log(f"{name}: {out[name]}")
    h, m = cache_counts()
    out.update(cache_hits=h, cache_misses=m)
    return out


# --------------------------------------------------------------------------
# phase mesh4 — one process, 2x2
# --------------------------------------------------------------------------


def phase_mesh4(scale: int, seed: int) -> dict:
    dev = start_phase()
    import jax
    import numpy as np

    from combblas_tpu.parallel.grid import Grid

    check(dev["n_devices"] >= 4, f"mesh4 needs 4 chips, have {dev['n_devices']}")
    out = dict(dev, scale=scale)
    t0 = time.perf_counter()
    n, rows, cols, w, keys = build_graph(scale, seed)
    _, roots = pick_roots(rows, n, seed, 16)
    out.update(nnz=int(len(rows)), build_s=round(time.perf_counter() - t0, 2))
    log(f"graph: n={n} nnz={len(rows)} build_s={out['build_s']}")

    grid = Grid.make(2, 2)
    engine, srv, mark = load_and_warm(
        grid, rows, cols, n, (1, 16), out, kinds=("bfs",)
    )
    t0 = time.perf_counter()
    got = results([srv.submit("bfs", int(r)) for r in roots])
    out["query_s"] = {"bfs": round(time.perf_counter() - t0, 3)}
    check(engine.stats()["plans"]["bfs/16"]["executions"] >= 1,
          "the width-16 BFS lane executed")
    check_server_clean(srv, engine, mark)
    srv.close()

    # placement: operands and results sharded over four distinct devices,
    # nothing larger than a vector block wholly on one device
    four = lambda arr: len({s.device for s in arr.addressable_shards}) == 4
    for cls, bucket in enumerate(engine.E.buckets):
        for arr in bucket:
            check(four(arr), f"E bucket class {cls}: shards on 4 devices")
    p, l, *_ = engine.plan("bfs", 16).fn(np.asarray(roots, np.int32))
    check(four(p) and four(l), "result blocks: shards on 4 devices")
    block_bytes = grid.local_rows(n) * 16 * 4
    whole = [
        (a.shape, str(a.dtype), a.nbytes) for a in jax.live_arrays()
        if len(a.sharding.device_set) == 1 and a.nbytes > block_bytes
    ]
    check(not whole, f"arrays larger than a vector block on ONE device: {whole}")
    out["dev_bytes_in_use"] = [
        int((d.memory_stats() or {}).get("bytes_in_use", -1))
        for d in jax.devices()[:4]
    ]
    del p, l

    G = ref_graph(n, rows, cols, w)
    for i in range(2):
        lv = ref_bfs_levels(G, int(roots[i]))
        check(np.array_equal(got[i]["levels"], lv),
              f"2x2 served BFS levels exact, root {int(roots[i])}")
        check_tree(lv, got[i]["parents"], int(roots[i]), keys, n,
                   f"2x2 served BFS root {int(roots[i])}")
    return out


# --------------------------------------------------------------------------
# --fleet: seed child (one chip), then a router that holds none
# --------------------------------------------------------------------------


def phase_fleetseed(scale: int, seed: int, workdir: str) -> dict:
    dev = start_phase()
    import numpy as np

    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import GraphEngine
    from combblas_tpu.utils import checkpoint

    n, rows, cols, _, _ = build_graph(scale, seed)
    _, roots = pick_roots(rows, n, seed, 1)
    engine = GraphEngine.from_coo(
        Grid.make(1, 1), rows, cols, n, kinds=("bfs",), keep_coo=True
    )
    res = engine.execute("bfs", np.asarray([roots[0]], np.int32))
    np.save(os.path.join(workdir, "levels.npy"), res["levels"][:, 0])
    checkpoint.save_version(
        os.path.join(workdir, "version.npz"), engine.version
    )
    return dict(dev, scale=scale, nnz=int(len(rows)), root=int(roots[0]))


def phase_fleet(workdir: str, root: int) -> dict:
    import numpy as np

    from combblas_tpu.serve import ServeConfig
    from combblas_tpu.serve.procfleet import ProcessFleet

    want = np.load(os.path.join(workdir, "levels.npy"))
    # boot and ipc deadlines sized for a scale-20 checkpoint load plus a
    # cold compile, not for the scale-8 CPU boots the defaults assume
    fleet = ProcessFleet.from_checkpoint(
        os.path.join(workdir, "version.npz"), (1, 1), replicas=2,
        kinds=("bfs",), config=ServeConfig(lane_widths=LANE_WIDTHS),
        wal_dir=os.path.join(workdir, "wal"),
        workdir=os.path.join(workdir, "fleet"),
        boot_timeout_s=600.0, ipc_timeout_s=300.0,
    )
    try:
        boots = [rp.boot_info for rp in fleet.replicas]
        log(f"replica boots: {boots}")
        for i, bi in enumerate(boots):
            check(bi["platform"] == "tpu" and bi["devices"] == 1,
                  f"replica {i} owns exactly one TPU chip ({bi})")
            check("error" not in bi["warmed"], f"replica {i} warmed ({bi})")
        # the chip each replica holds, as the kernel sees it: the device
        # nodes open in that process
        nodes = []
        for bi in boots:
            fd_dir = f"/proc/{bi['pid']}/fd"
            nodes.append(sorted({
                t for t in (
                    os.readlink(os.path.join(fd_dir, f))
                    for f in os.listdir(fd_dir)
                ) if t.startswith(("/dev/accel", "/dev/vfio/"))
                and t != "/dev/vfio/vfio"
            }))
        log(f"replica device nodes: {nodes}")
        check(nodes[0] and nodes[1] and not set(nodes[0]) & set(nodes[1]),
              f"replicas hold distinct chips ({nodes})")
        got = [fleet.submit("bfs", root).result(timeout=RESULT_TIMEOUT_S)]
        got += [
            rp.submit("bfs", root).result(timeout=RESULT_TIMEOUT_S)
            for rp in fleet.replicas
        ]
        for g in got:
            check(np.array_equal(np.asarray(g["levels"]), want),
                  "replica BFS answer equals the in-process one")
    finally:
        fleet.close()
    from jax._src import xla_bridge

    check(not xla_bridge.backends_are_initialized(),
          "the router started no backend (holds no chip)")
    return {
        "replicas": 2,
        "device_ids": [bi["device_ids"] for bi in boots],
        "device_nodes": nodes,
        "device_kind": boots[0]["device_kind"],
    }


# --------------------------------------------------------------------------
# parent
# --------------------------------------------------------------------------


def run_child(phase: str, *extra: str) -> dict:
    """One phase in a fresh process; its last stdout line is its result.
    A child that exits non-zero fails the run."""
    log(f"phase {phase}: start")
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--phase", phase, *extra],
        stdout=subprocess.PIPE, text=True, timeout=PHASE_TIMEOUT_S,
    )
    if r.returncode != 0:
        raise SystemExit(
            f"chip_smoke: phase {phase} exited {r.returncode}"
        )
    out = json.loads(r.stdout.strip().splitlines()[-1])
    out["wall_s"] = round(time.perf_counter() - t0, 1)
    log(f"phase {phase}: ok in {out['wall_s']} s: {json.dumps(out)}")
    return out


DEVICE_KEYS = ("platform", "device_kind", "n_devices", "jax", "jaxlib",
               "libtpu", "cache_dir")


def emit_result(first: dict, phases: dict) -> None:
    """Every phase passed.  Two stdout lines: the summary (the device as
    JAX reported it to the first phase, then each phase without the
    repeated device fields), and LAST the verdict, which carries exactly
    ``ok`` and ``device``."""
    strip = lambda d: (
        {k: v for k, v in d.items() if k not in DEVICE_KEYS}
        if isinstance(d, dict) else d
    )
    summary = {
        "ok": True,
        **{k: first[k] for k in DEVICE_KEYS},
        "scale": first.get("scale"),
        "nnz": first.get("nnz"),
        **{name: strip(res) for name, res in phases.items()},
        "claim": None,
    }
    verdict = {
        "ok": True,
        "device": {
            "platform": str(first["platform"]),
            "kind": str(first["device_kind"]),
            "count": int(first["n_devices"]),
        },
    }
    print(json.dumps(summary), flush=True)
    print(json.dumps(verdict), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, help="development: graph scale")
    ap.add_argument("--seed", type=int, default=SEED, help="development")
    ap.add_argument("--fleet", action="store_true",
                    help="the 2-replica ProcessFleet proof (>= 2 chips)")
    ap.add_argument("--phase", help="internal: run one phase in-process")
    ap.add_argument("--workdir", help="internal")
    ap.add_argument("--root", type=int, help="internal")
    args = ap.parse_args()
    dev_args = ["--seed", str(args.seed)] + (
        ["--scale", str(args.scale)] if args.scale else []
    )

    if args.phase:
        scale = args.scale or (
            SCALE_4CHIP if args.phase == "mesh4" else SCALE_1CHIP
        )
        if args.phase == "serve1":
            res = phase_serve1(scale, args.seed)
        elif args.phase == "kernels":
            res = phase_kernels()
        elif args.phase == "mesh4":
            res = phase_mesh4(scale, args.seed)
        elif args.phase == "fleetseed":
            res = phase_fleetseed(scale, args.seed, args.workdir)
        elif args.phase == "fleet":
            res = phase_fleet(args.workdir, args.root)
        else:
            ap.error(f"unknown phase {args.phase!r}")
        print(json.dumps(res), flush=True)
        return 0

    if args.fleet:
        workdir = tempfile.mkdtemp(prefix="chip_smoke_fleet_")
        try:
            seed = run_child("fleetseed", "--workdir", workdir, *dev_args)
            fleet = run_child("fleet", "--workdir", workdir,
                              "--root", str(seed["root"]))
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        emit_result(seed, {"fleet": fleet})
        return 0

    serve1 = run_child("serve1", *dev_args)
    phases = {"serve1": serve1, "kernels": run_child("kernels")}
    if serve1["n_devices"] >= 4:
        phases["mesh4"] = run_child("mesh4", "--seed", str(args.seed), *(
            ["--scale", str(args.scale + 2)] if args.scale else []
        ))
    else:
        phases["mesh4"] = f"skipped: {serve1['n_devices']} chip"
    emit_result(serve1, phases)
    return 0


if __name__ == "__main__":
    sys.exit(main())
