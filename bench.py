"""Graph500 BFS benchmark (kernel 2), one chip, R-MAT scale BENCH_SCALE.

OUTPUT. Incremental JSON lines: the full official record is re-printed,
enriched, as the protocol progresses (a run cut short still leaves a
complete, parseable record), and the LAST stdout line is a compact
summary, mirrored to BENCH_SUMMARY.json:
  {"summary": 1, "metric": ..., "value": N, "median": N, "warning": ...,
   "platform": ..., "device_kind": ..., "n_devices": N, "rc": 0}
The process exit code equals "rc".

PROCESSES. A chip belongs to one process at a time. The parent builds
the graph and the search structures on the host (numpy only; importing
the package starts no backend) and never touches a device; every
measurement is a child process, run one after the other, so the chip
always has exactly one owner. Every child line names the device it ran
on ("platform", "device_kind", "n_devices"). "rc" is non-zero when no
repeat produced a measurement, or when the children ran on anything but
a TPU without JAX_PLATFORMS=cpu having been given explicitly: JAX falls
back to the CPU with a warning when it finds no accelerator, and that
must never pass for a chip run. The metric name says "1chip" only when
the platform is "tpu".

PROTOCOL (adapted from the reference's TopDownBFS driver,
TopDownBFS.cpp:421-479): R-MAT scale-S graph (edgefactor 16, symmetrized,
deloop'd, dedup'd), BFS from NROOTS random reachable roots.
  * BATCH REPEATS: BENCH_REPEATS (default 3) subprocess repeats of the
    W=NROOTS batched BFS (models/bfs.py:bfs_batch_compact, int8 level
    frontiers + one-pass parent reconstruction). Each child: host load,
    one upload, an untimed warmup launch (compile + execute, recorded
    as warmup_s), a BENCH_DRAIN_S sleep, then ONE timed launch closed
    by the traversed-edge readback (batch_traversed_edges, a [W]
    vector). AGGREGATE MTEPS = sum of kernel-2 traversed edges / wall.
    The record carries every repeat and their median. A repeat that
    FAILED gets exactly one replacement; the original stays in "runs".
  * SEQUENTIAL ROOTS (the spec's statistic, TopDownBFS.cpp:437-479):
    BENCH_SEQ_ROOTS (default 16) further children each time ONE root
    with models/bfs.py:bfs_single (frontier-proportional tiers,
    BENCH_SEQ_TIERS) after one untimed warmup child has filled the
    compile cache. Their harmonic-mean MTEPS is the only number
    comparable with BASELINE.md; it becomes "value" once >= 4 roots
    were timed, the batch median otherwise ("statistic" says which;
    "batch_median_mteps" is always alongside).
  * BUDGET: repeats always run; sequential roots run while they fit
    BENCH_BUDGET_S (default 1200 s). "seq_roots_timed" records how
    many fit.
  * PER-ROOT AMORTIZED STATISTIC: every level's gather serves all W
    roots at once, so each root's attributed time is dt/W:
    TEPS_r = te_r * W / dt, harmonic mean over live roots
    ("harmonic_mean_amortized_mteps") — a property of the batched
    design, not the spec's statistic.
  * VALIDATION (BENCH_VALIDATE, default on): each repeat child runs the
    device-side Graph500 tree checks (validate_bfs_device) on a lane
    subset AFTER its timed readback; the first timed sequential root
    validates too. "validated" covers every successful repeat.
  * KERNEL 1: construction_s = host R-MAT + dedup + ELL bucketing + CSC
    companion, built once in the parent and shipped to the children as
    an .npz. BENCH_K1=device instead runs the distributed device
    pipeline (models/graph500.py:kernel1_device) in a child of its own.
  * COMPILE CACHE: every child calls enable_compile_cache()
    (utils/compile_cache.py: JAX_COMPILATION_CACHE_DIR when set, else
    <checkout>/.jax_cache), so children share compiled programs with
    each other and with earlier runs.

The drains, the process per timed root and the warmup child were sized
in rounds 2-5 on a machine that is gone; they are kept as they are
until the protocol is re-measured on today's (ROADMAP S1/S3/D9).

vs_baseline compares single-chip MTEPS against the smallest archived
reference run: 1,636 MTEPS on 1,024 Hopper (Cray XE6) cores
(BASELINE.md: HopperResults/script1024.reducedgraph_mini:149).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

SCALE = int(os.environ.get("BENCH_SCALE", "20"))
EDGEFACTOR = int(os.environ.get("BENCH_EDGEFACTOR", "16"))
NROOTS = int(os.environ.get("BENCH_NROOTS", "256"))
DIROPT = os.environ.get("BENCH_DIROPT", "0") == "1"
REPEATS = int(os.environ.get("BENCH_REPEATS", "3"))
DRAIN_S = float(os.environ.get("BENCH_DRAIN_S", "45"))
# each repeat child runs the device-side Graph500 checks after its
# timed readback, so the reported median is a validated number
VALIDATE = os.environ.get("BENCH_VALIDATE", "1") == "1"
# the spec's SEQUENTIAL per-root statistic (TopDownBFS.cpp:437-479):
# BENCH_SEQ_ROOTS extra children each time ONE root in its own process
SEQ_ROOTS = int(os.environ.get("BENCH_SEQ_ROOTS", "16"))
SEQ_DRAIN_S = float(os.environ.get("BENCH_SEQ_DRAIN_S", "10"))
# wall-clock budget the whole protocol must fit; repeats always run,
# sequential roots fill the remainder
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1200"))
# class-budget tier ladder for the sequential child (see
# models/bfs.py:parse_tier_spec; importing the package starts no backend)
from combblas_tpu.models.bfs import DEFAULT_SEQ_TIERS  # noqa: E402

SEQ_TIERS = os.environ.get("BENCH_SEQ_TIERS", DEFAULT_SEQ_TIERS)
BASELINE_MTEPS = 1636.0  # Hopper 1024 cores, R-MAT "mini"


def _enable_compile_cache():
    """Persistent compilation cache (see utils/compile_cache.py):
    children share compiled programs with each other and with prior
    runs, so the 16 sequential-root processes compile bfs_single exactly
    once. BENCH_NOCACHE=1 disables (diagnostic)."""
    from combblas_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()


def _obs_setup(tag: str) -> str | None:
    """BENCH_OBS=1: enable the structured telemetry subsystem in this
    process (combblas_tpu.obs; docs/observability.md) with a per-process
    JSONL sidecar — spans for the load/warmup/timed phases, compile-cache
    hit/miss counters, kernel dispatch counts. The official stdout JSON
    protocol is unchanged; each child reports its sidecar path under
    "obs_jsonl" and the parent merges them (the multihost-style
    per-process-files-merged-host-side aggregation path).

    DEVICE_SYNC stays OFF here: obs must never add a readback to a
    timed child."""
    from combblas_tpu import obs

    return obs.enable_sidecar(tag)


def _child_line(out: dict) -> None:
    """Print one child's JSON line: the device it ran on (every line
    names it), plus the telemetry sidecar when enabled."""
    from combblas_tpu import obs
    from combblas_tpu.utils import device_fields

    out.update(device_fields())
    if obs.ENABLED:
        try:
            out["obs_jsonl"] = obs.dump_jsonl()
        except Exception as e:  # telemetry must never fail the bench
            out["obs_error"] = str(e)
    print(json.dumps(out), flush=True)


def build_graph_npz(path: str) -> float:
    """Kernel 1, host path: R-MAT generate + symmetricize + dedup; returns
    construction seconds (graph build only; the search structures are
    added by augment_npz_with_structures and timed separately)."""
    import numpy as np

    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    t0 = time.perf_counter()
    n = 1 << SCALE
    rows, cols = rmat_symmetric_coo_host(42, SCALE, EDGEFACTOR)
    key = rows * np.int64(n) + cols
    uniq = np.unique(key)
    rows_u = (uniq // n).astype(np.int64)
    cols_u = (uniq % n).astype(np.int64)
    deg = np.bincount(rows_u, minlength=n)
    dt = time.perf_counter() - t0
    rng = np.random.default_rng(7)
    roots = rng.choice(np.flatnonzero(deg > 0), size=NROOTS, replace=False)
    np.savez(
        path,
        rows=rows_u.astype(np.int32),  # scale <= 31 fits; halves the file
        cols=cols_u.astype(np.int32),
        deg=deg.astype(np.int32),
        roots=roots.astype(np.int32),
    )
    return dt


def augment_npz_with_structures(path: str) -> float:
    """Kernel-1 tail, host: build the ELL buckets + CSC companion ONCE in
    the parent (numpy only — the parent never attaches to the chip) and
    append them to the graph .npz, so every timing child just uploads.
    Returns build seconds (counted into construction_s: the reference's
    kernel 1 likewise includes assembling the search structure,
    SpParMat.cpp:3343 OptimizeForGraph500)."""
    import numpy as np

    from combblas_tpu.parallel.ellmat import (
        EllParMat,
        build_csc_companion_host,
    )
    from combblas_tpu.parallel.grid import HostGrid

    t0 = time.perf_counter()
    z = dict(np.load(path))
    grid = HostGrid(1, 1)
    n = 1 << SCALE
    buckets = EllParMat.host_build(
        grid, z["rows"], z["cols"],
        np.zeros(len(z["rows"]), np.int8), n, n,
    )
    indptr, rowidx = build_csc_companion_host(
        grid, z["rows"], z["cols"], n, n
    )
    z["csc_indptr"], z["csc_rowidx"] = indptr, rowidx
    z["nnz"] = np.int64(len(z["rows"]))
    z["ell_nbuckets"] = np.int32(len(buckets))
    for b, (bc, _bv, br) in enumerate(buckets):
        z[f"ell{b}_bc"] = bc
        z[f"ell{b}_br"] = br
    np.savez(path, **z)
    return time.perf_counter() - t0


def k1_device_child(path: str):
    """Kernel 1, DISTRIBUTED device path (VERDICT r3 item 7): run
    ``models/graph500.py:kernel1_device`` on the chip in THIS dedicated
    process, serialize the graph for the BFS children, and report
    per-stage construction timings.  This makes
    the official construction_s the distributed pipeline's number
    (SpParMat.cpp:3140-3441 role) instead of the host numpy path."""
    _enable_compile_cache()
    _obs_setup("k1")
    import jax
    import numpy as np

    from combblas_tpu.models.graph500 import kernel1_device
    from combblas_tpu.parallel.grid import Grid

    def log(msg):
        print(f"[k1] {time.strftime('%H:%M:%S')} {msg}",
              file=sys.stderr, flush=True)

    grid = Grid.make(1, 1)
    n = 1 << SCALE
    # warmup pass: compiles every stage; the timed pass below then
    # measures construction EXECUTION, matching the host path's
    # semantics (the reference doesn't time compilation)
    log("warmup start")
    _, _, _, wt = kernel1_device(
        grid, SCALE, EDGEFACTOR, jax.random.PRNGKey(41),
        compress_isolated=False,
    )
    log(f"warmup done {[ (k, round(v,1)) for k,v in wt.items() if k != 'dropped_dev' ]}")
    time.sleep(float(os.environ.get("BENCH_K1_DRAIN_S", "15")))
    t0 = time.perf_counter()
    A, degrees, _nkeep, timings = kernel1_device(
        grid, SCALE, EDGEFACTOR, jax.random.PRNGKey(42),
        compress_isolated=False,
    )
    construction_s = time.perf_counter() - t0
    log(f"timed pass done {construction_s:.1f}s")
    # post-timing verification (first readback of this process): the
    # deferred route-capacity drop count must be zero or the build is
    # invalid and the parent falls back to the host kernel 1
    dropped = int(np.asarray(jax.device_get(timings.pop("dropped_dev"))))
    if dropped != 0:
        raise SystemExit(f"kernel1_device dropped {dropped} tuples")
    log("drop check ok; D2H start")
    # D2H serialization (untimed: the reference hands kernel 1's output to
    # kernel 2 in-memory; here it crosses a process boundary)
    t = A.local_tile(A.rows, A.cols, A.vals, A.nnz)
    rows = np.asarray(jax.device_get(t.rows))
    log("rows fetched")
    cols = np.asarray(jax.device_get(t.cols))
    log("cols fetched")
    live = rows < n
    rows_u, cols_u = rows[live], cols[live]
    deg = np.asarray(jax.device_get(degrees.blocks)).reshape(-1)[:n]
    log("deg fetched; writing npz")
    rng = np.random.default_rng(7)
    roots = rng.choice(np.flatnonzero(deg > 0), size=NROOTS, replace=False)
    np.savez(
        path,
        rows=rows_u.astype(np.int32),
        cols=cols_u.astype(np.int32),
        deg=deg.astype(np.int32),
        roots=roots.astype(np.int32),
    )
    out = {
        "construction_s": round(construction_s, 2),
        "stages": {k: round(v, 3) for k, v in timings.items()},
        "nnz": int(len(rows_u)),
    }
    _child_line(out)


def _load_structures(grid, data, n, want_csc=True):
    """Upload the parent-prebuilt ELL buckets (+ CSC companion when the
    caller walks columns — ``want_csc=False`` skips its ~4B/nnz upload
    in the plain batched repeats) from the .npz, falling back to
    in-child construction for an un-augmented graph file."""
    import numpy as np

    from combblas_tpu.parallel.ellmat import (
        EllParMat,
        build_csc_companion,
        upload_csc_companion,
    )

    if "ell_nbuckets" in data:
        nb = int(data["ell_nbuckets"])
        host_buckets = [
            (
                data[f"ell{b}_bc"],
                np.zeros(data[f"ell{b}_bc"].shape, np.int8),
                data[f"ell{b}_br"],
            )
            for b in range(nb)
        ]
        E = EllParMat.from_host_buckets(grid, host_buckets, n, n)
        csc = (
            upload_csc_companion(
                grid, data["csc_indptr"], data["csc_rowidx"]
            )
            if want_csc else None
        )
    else:
        rows_u, cols_u = data["rows"], data["cols"]
        E = EllParMat.from_host_coo(
            grid, rows_u, cols_u,
            np.zeros(len(rows_u), np.int8), n, n,
        )
        csc = (
            build_csc_companion(grid, rows_u, cols_u, n, n)
            if want_csc else None
        )
    return E, csc


def seq_child(graph_path: str, seq_idx: int):
    """Sequential-statistic child: ONE root, frontier-proportional
    tiered BFS (bfs_single), one launch, own process."""
    _enable_compile_cache()
    _obs_setup(f"seq{seq_idx}")
    import jax
    import numpy as np

    from combblas_tpu import obs
    from combblas_tpu.models.bfs import bfs_single, single_traversed_edges
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.vec import DistVec

    grid = Grid.make(1, 1)
    n = 1 << SCALE

    t0 = time.perf_counter()
    with obs.span("bench.load"):
        data = np.load(graph_path)
        root = np.int32(data["roots"][seq_idx])
        E, csc = _load_structures(grid, data, n)
        deg_blocks = DistVec.from_global(
            grid, data["deg"], align="row"
        ).blocks
        # symmetric graph: per-column degrees == per-row degrees;
        # host-built (deriving them from the CSC indptr on device was a
        # slow megascale 1-D op in round 5, probe_seq_r5 mode v6; not
        # re-measured)
        coldeg_blocks = DistVec.from_global(
            grid, data["deg"], align="col"
        ).blocks
    from combblas_tpu.models.bfs import parse_tier_spec

    tiers = parse_tier_spec(SEQ_TIERS)
    construction_child_s = time.perf_counter() - t0

    # csr=csc REUSE CONTRACT (ADVICE r5): bfs_single's "bu" tiers walk the
    # CSR companion, and reusing the CSC there is correct ONLY because
    # (a) the Graph500 graph is SYMMETRIZED — in-edges equal out-edges, so
    # the column-major companion doubles as the row-major one — and
    # (b) the grid is 1x1, so build_csr_companion's per-tile layout
    # degenerates to the same single global array. An asymmetric graph or
    # a multi-chip grid must build the real companion
    # (ellmat.build_csr_companion / a csr twin in
    # augment_npz_with_structures) — fail loudly rather than traverse
    # wrong in-edges.
    assert grid.pr == 1 and grid.pc == 1, (
        "seq_child reuses csr=csc, valid only on a 1x1 grid with a "
        "symmetrized graph; build the real CSR companion for "
        f"{grid.pr}x{grid.pc}"
    )

    # warmup (compile via the persistent cache + one full execution)
    t0 = time.perf_counter()
    with obs.span("bench.warmup"):
        p, _, _ = bfs_single(E, root, csc, csr=csc, tiers=tiers,
                             coldeg=coldeg_blocks, rowdeg=deg_blocks)
        te_dev = single_traversed_edges(deg_blocks, p)
        jax.block_until_ready(te_dev)
    warmup_s = time.perf_counter() - t0
    time.sleep(SEQ_DRAIN_S)

    t0 = time.perf_counter()
    with obs.span("bench.timed", root_index=int(seq_idx)):
        p, l, niter = bfs_single(E, root, csc, csr=csc, tiers=tiers,
                                 coldeg=coldeg_blocks, rowdeg=deg_blocks)
        te_dev = single_traversed_edges(deg_blocks, p)
        te = int(np.asarray(jax.device_get(te_dev)))  # true barrier
    dt = time.perf_counter() - t0
    obs.span_event("bfs.result", traversed_edges=te, root_index=int(seq_idx))

    out = {
        "mteps": round(te / dt / 1e6, 4),
        "dt_s": round(dt, 4),
        "warmup_s": round(warmup_s, 2),
        "drain_s": SEQ_DRAIN_S,
        "total_traversed_edges": te,
        "levels": int(np.asarray(jax.device_get(niter))),
        "root_index": int(seq_idx),
        "construction_child_s": round(construction_child_s, 2),
    }
    if VALIDATE and os.environ.get("BENCH_SEQ_VALIDATE_THIS") == "1":
        # the headline statistic's kernel gets the same device-side tree
        # checks as the batch path (predeclared: the FIRST timed root
        # validates, after its timed readback)
        import jax.numpy as jnp

        from combblas_tpu.models.bfs import validate_bfs_device
        from combblas_tpu.parallel.vec import DistMultiVec

        mv = lambda v, dt_: DistMultiVec(
            blocks=v.blocks[:, :, None].astype(dt_), length=v.length,
            align=v.align, grid=v.grid,
        )
        v = np.asarray(jax.device_get(validate_bfs_device(
            E, mv(p, jnp.int32), mv(l, jnp.int32)
        )))
        out["validation"] = {
            "roots_bad": int(v[0].sum()),
            "level_step_bad": int(v[1].sum()),
            "tree_edge_bad": int(v[2].sum()),
            "edge_consistency_bad": int(v[3].sum()),
        }
    _child_line(out)


def child(graph_path: str):
    _enable_compile_cache()
    _obs_setup("batch")
    import jax
    import numpy as np

    from combblas_tpu import obs

    from combblas_tpu.models.bfs import batch_traversed_edges, bfs_batch_compact
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.vec import DistVec

    grid = Grid.make(1, 1)
    n = 1 << SCALE

    # --- Phase 1+2: host-only load, then upload (H2D only) ----------------
    t0 = time.perf_counter()
    with obs.span("bench.load"):
        data = np.load(graph_path)
        deg, roots = data["deg"], data["roots"]
        nnz = (
            int(data["nnz"]) if "nnz" in data else len(data["rows"])
        )
        E, csc_arrays = _load_structures(grid, data, n, want_csc=DIROPT)
        csc = None
        fcap = ecap = None
        if DIROPT:
            csc = csc_arrays
            fcap = grid.local_cols(n) // 8
            ecap = max(nnz // 16, 1 << 20)
        deg_blocks = DistVec.from_global(grid, deg, align="row").blocks
        roots_dev = jax.device_put(np.asarray(roots, np.int32))
    construction_child_s = time.perf_counter() - t0

    # --- Phase 3: ONE timed launch ----------------------------------------
    # Warmup compiles AND executes the whole batched program; the
    # drain sleep separates it from the timed launch (DRAIN_S=45 was
    # sized in rounds 2-5 on a machine that is gone; not re-measured).
    t0 = time.perf_counter()
    with obs.span("bench.warmup"):
        p, _, _ = bfs_batch_compact(
            E, roots_dev, csc=csc, frontier_capacity=fcap, edge_capacity=ecap
        )
        te_dev = batch_traversed_edges(deg_blocks, p)
        jax.block_until_ready(te_dev)
    warmup_s = time.perf_counter() - t0
    time.sleep(DRAIN_S)

    t0 = time.perf_counter()
    with obs.span("bench.timed", roots=int(len(roots))):
        parents, levels, _ = bfs_batch_compact(
            E, roots_dev, csc=csc, frontier_capacity=fcap, edge_capacity=ecap
        )
        te_dev = batch_traversed_edges(deg_blocks, parents)
        te = np.asarray(jax.device_get(te_dev))  # the barrier
    dt = time.perf_counter() - t0

    validation = None
    if VALIDATE:
        # Graph500 tree validation ON DEVICE (verify.c intent) — after the
        # timed section.  Validates a LANE SUBSET: the validator's bucket-sweep
        # intermediates scale with slots x lanes (~46 GB at W=256 on
        # scale 20 — past HBM), so a handful of lanes is the memory-sane
        # spot check (BENCH_VALIDATE_LANES, default 4).
        from combblas_tpu.models.bfs import validate_bfs_device

        import jax.numpy as jnp

        nl = min(int(os.environ.get("BENCH_VALIDATE_LANES", "4")), len(te))

        def lanes(mv, dtype=None):
            b = mv.blocks[:, :, :nl]
            return type(mv)(
                blocks=b.astype(dtype) if dtype is not None else b,
                length=mv.length, align=mv.align, grid=mv.grid,
            )

        v = np.asarray(
            jax.device_get(
                validate_bfs_device(
                    E, lanes(parents), lanes(levels, jnp.int32)
                )
            )
        )
        validation = {
            "lanes_checked": nl,
            "roots_bad": int(v[0].sum()),
            "level_step_bad": int(v[1].sum()),
            "tree_edge_bad": int(v[2].sum()),
            "edge_consistency_bad": int(v[3].sum()),
        }

    # --- Phase 4: accounting ----------------------------------------------
    total_te = int(te.astype(np.int64).sum())
    W = len(te)
    mteps = total_te / dt / 1e6
    live = te[te > 0].astype(np.float64)
    hm = (
        (len(live) * W / (dt * np.sum(1.0 / live)) / 1e6)
        if len(live) else 0.0
    )
    out = {
        "mteps": round(mteps, 2),
        "harmonic_mean_amortized_mteps": round(float(hm), 2),
        "dt_s": round(dt, 3),
        "warmup_s": round(warmup_s, 2),
        "drain_s": DRAIN_S,
        "total_traversed_edges": total_te,
        "roots": int(W),
        "reachable_roots": int((te > 0).sum()),
        "construction_child_s": round(construction_child_s, 2),
    }
    if validation is not None:
        out["validation"] = validation
    _child_line(out)


DEVICE_KEYS = ("platform", "device_kind", "n_devices")


def platform_error(platform) -> str | None:
    """Why a record measured on ``platform`` must not pass (None = it
    may): anything but a TPU is accepted only when the CPU was asked
    for by name — JAX falls back to it silently-with-a-warning when it
    finds no accelerator."""
    from combblas_tpu.utils import inherited_platform

    asked = inherited_platform()
    if platform == "tpu" or (platform == "cpu" and asked == "cpu"):
        return None
    return (
        f"children ran on platform {platform!r} but JAX_PLATFORMS="
        f"{asked or '<unset>'}: not a chip measurement (give "
        "JAX_PLATFORMS=cpu explicitly for a CPU run)"
    )


def emit_summary(official, rc: int | None = None,
                 path: str | None = None) -> int:
    """Print the COMPACT headline summary as the FINAL stdout line and
    mirror it to ``BENCH_SUMMARY.json``: tail truncation of the giant
    per-run record once ate a capture's headline — a short final line
    plus a sidecar file cannot lose it.  The full record stays on the
    earlier lines (``emit``).  ``rc`` defaults to the record's own
    verdict (non-zero iff it carries ``"error"``) and is returned: it
    is the process exit code."""
    official = official or {}
    if rc is None:
        rc = 1 if official.get("error") else 0
    s = {
        "summary": 1,
        "metric": official.get("metric"),
        "value": official.get("value", 0.0),
        "median": official.get(
            "batch_median_mteps", official.get("value", 0.0)
        ),
        "warning": (
            official.get("warning") or official.get("error") or ""
        )[-300:] or None,
        **{k: official.get(k) for k in DEVICE_KEYS},
        "rc": rc,
    }
    # round-10 plan provenance (store hit vs probe vs heuristic + the
    # chosen knobs) rides along when the child reported it — still a
    # compact, truncation-proof line
    for k in ("plan_source", "plan"):
        if official.get(k) is not None:
            s[k] = official[k]
    path = path or os.environ.get("BENCH_SUMMARY_PATH", "BENCH_SUMMARY.json")
    try:
        with open(path, "w") as f:
            json.dump(s, f)
            f.write("\n")
    except OSError as e:
        s["summary_write_error"] = f"{path}: {e}"
    print(json.dumps(s), flush=True)
    return rc


def emit(runs, seq_runs, construction_s, k1_info, t_start):
    """Assemble and PRINT (flushed) the official JSON line from whatever
    has completed so far — called after the repeat phase and again after
    every sequential-root child, so a run cut short at any point still
    leaves a complete last line. Returns the dict it printed (the
    parent's ``emit_summary`` source)."""
    ok = sorted(
        (r for r in runs if r.get("mteps", 0) > 0), key=lambda r: r["mteps"]
    )
    # median REPEAT: value and the per-root statistic come from the same run
    med_run = ok[(len(ok) - 1) // 2] if ok else {}
    median = med_run.get("mteps", 0.0)
    # Graph500-spec sequential statistic: harmonic mean of per-root TEPS
    # over the individually-timed roots (each its own process)
    seq_ok = [
        r for r in seq_runs
        if r.get("mteps", 0) > 0 and r.get("total_traversed_edges", 0) > 0
    ]
    seq_hm = (
        len(seq_ok) / sum(1.0 / r["mteps"] for r in seq_ok) if seq_ok else 0.0
    )
    # HEADLINE RULE (docstring): the spec's sequential statistic is the
    # value once >= 4 roots are individually timed; the amortized batch
    # median otherwise (and always alongside as batch_median_mteps).
    spec_headline = len(seq_ok) >= 4
    value = seq_hm if spec_headline else median
    # the device the CHILDREN report (the parent never starts a backend)
    dev = {k: med_run.get(k) for k in DEVICE_KEYS}
    where = "1chip" if dev["platform"] == "tpu" else (
        dev["platform"] or "unmeasured"
    )
    out = {
        "metric": f"graph500_bfs_rmat_scale{SCALE}_{where}_MTEPS",
        **dev,
        "value": round(value, 2),
        "unit": "MTEPS",
        "vs_baseline": round(value / BASELINE_MTEPS, 6),
        "statistic": (
            "seq_per_root_harmonic_mean" if spec_headline
            else "amortized_batch_median"
        ),
        "batch_median_mteps": round(median, 2),
        "batch_vs_baseline": round(median / BASELINE_MTEPS, 4),
        "repeats_mteps": [r.get("mteps", 0.0) for r in runs],
        "harmonic_mean_amortized_mteps": med_run.get(
            "harmonic_mean_amortized_mteps", 0.0
        ),
        "seq_harmonic_mean_mteps": round(seq_hm, 3),
        "seq_roots_timed": len(seq_ok),
        "seq_roots_planned": min(SEQ_ROOTS, NROOTS),
        "seq_per_root_mteps": [r.get("mteps", 0.0) for r in seq_runs],
        "seq_vs_baseline": round(seq_hm / BASELINE_MTEPS, 6),
        "construction_s": round(construction_s, 2),
        "construction": k1_info,
        "validation": med_run.get("validation"),
        "seq_validation": next(
            (r["validation"] for r in seq_ok if r.get("validation")), None
        ),
        "validated": bool(
            ok
            and all(
                r.get("validation") is not None
                and not any(
                    v for k, v in r["validation"].items() if k.endswith("_bad")
                )
                for r in ok
            )
            # when the headline IS the seq statistic, its kernel's tree
            # check must also be clean
            and (
                not spec_headline
                or any(
                    r.get("validation") is not None
                    and not any(
                        v for k, v in r["validation"].items()
                        if k.endswith("_bad")
                    )
                    for r in seq_ok
                )
            )
        ),
        "budget_s": BUDGET_S,
        "elapsed_s": round(time.perf_counter() - t_start, 1),
        "runs": runs,
        "seq_runs": seq_runs,
    }
    if ok:
        # median + spread of the (>= 3 by default) repeats
        vals = [r["mteps"] for r in ok]
        out["repeats_spread"] = {
            "min": round(min(vals), 2),
            "max": round(max(vals), 2),
            "rel_spread": round(
                (max(vals) - min(vals)) / max(median, 1e-9), 3
            ),
        }
    if not ok:
        out["error"] = (
            "no repeat produced a valid measurement; see 'runs' for "
            "per-child diagnostics"
        )
    else:
        bad = {
            platform_error(r.get("platform"))
            for r in ok + seq_ok
        } - {None}
        if bad:
            out["error"] = sorted(bad)[0]
    print(json.dumps(out), flush=True)
    return out


def serve_bench_main():
    """BENCH_SERVE=1: the query-serving benchmark
    (benchmarks/serve_bench.py — batched lanes vs one-call-per-query;
    the script defaults itself to the 8-virtual-device CPU mesh). The
    child emits its
    serve-throughput telemetry as a JSONL sidecar through the existing
    obs.enable_sidecar plumbing (BENCH_OBS defaults ON for this path;
    the sidecar path rides the JSON line as "obs_jsonl").  The chaos /
    mutate / pool scenario knobs (BENCH_SERVE_CHAOS, BENCH_SERVE_MUTATE,
    BENCH_SERVE_POOL — the round-14 multi-tenant scenario emits its own
    headline summary line too) pass through via the environment."""
    return _script_bench_main(
        "serve_bench.py", "serve_throughput",
        # every serve scenario reports its acceptance AND in "ok";
        # falling back to value covers a crashed child's stub dict
        rc_of=lambda out: out.get("ok", out.get("value", 0)),
        # the child's detail line must stay LAST under this runner:
        # the pool scenario's standalone summary line is suppressed
        extra_env={"BENCH_OBS": "1", "BENCH_EMIT_SUMMARY": "0"},
    )


def _script_bench_main(script_name: str, metric: str, rc_of,
                       extra_env: dict | None = None) -> int:
    """Shared child-runner for the script benches (serve_bench /
    spmm_bench): the platform passes through from THIS process's
    environment untouched (the scripts' own default is the virtual CPU
    mesh; their lines name the device they ran on), plus the timeout
    fallback and the JSON-tail guard (the official stream must stay
    one valid JSON line even when the child crashes or leaves stray
    stdout).  ``rc_of(out)`` maps the child's final dict to the
    summary rc, which is returned."""
    env = dict(os.environ)
    for k, v in (extra_env or {}).items():
        env.setdefault(k, v)
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "benchmarks", script_name,
    )
    try:
        r = subprocess.run(
            [sys.executable, script], capture_output=True, text=True,
            env=env,
            timeout=float(os.environ.get("BENCH_CHILD_TIMEOUT", "1800")),
        )
    except subprocess.TimeoutExpired as e:
        out = {
            "metric": metric, "value": 0.0,
            "error": f"{script_name} child timed out after {e.timeout}s",
        }
        print(json.dumps(out), flush=True)
        return emit_summary(out, rc=1)
    lines = [l for l in r.stdout.strip().splitlines() if l.strip()]
    try:
        if r.returncode != 0 or not lines:
            raise json.JSONDecodeError("child failed", "", 0)
        out = json.loads(lines[-1])
    except json.JSONDecodeError:
        out = {
            "metric": metric, "value": 0.0,
            "error": (r.stderr or "no output")[-2000:],
        }
    print(json.dumps(out), flush=True)
    return emit_summary(out, rc=0 if rc_of(out) else 1)


def spmm_bench_main():
    """BENCH_SPMM=1: the batched-SpMM benchmark
    (benchmarks/spmm_bench.py — fused k-hop sparse×dense vs
    loop-over-columns batch SpMV, scipy golden, and the serve
    "propagate" zero-retrace capture)."""
    return _script_bench_main(
        "spmm_bench.py", "spmm_khop_speedup",
        rc_of=lambda out: out.get("ok"),
    )


def main() -> int:
    """Run the mode the environment selects; returns the exit code (the
    summary's ``rc`` in parent modes, 0 for a child that printed its
    line — a child that fails raises)."""
    t_start = time.perf_counter()
    if os.environ.get("BENCH_SPMM") == "1":
        return spmm_bench_main()
    if os.environ.get("BENCH_SERVE") == "1":
        return serve_bench_main()
    if os.environ.get("BENCH_SEQ_ROOT_IDX") is not None:
        seq_child(
            os.environ["BENCH_GRAPH_NPZ"],
            int(os.environ["BENCH_SEQ_ROOT_IDX"]),
        )
        return 0
    if os.environ.get("BENCH_CHILD"):
        child(os.environ["BENCH_GRAPH_NPZ"])
        return 0
    if os.environ.get("BENCH_K1_CHILD"):
        k1_device_child(os.environ["BENCH_GRAPH_NPZ"])
        return 0

    import shutil

    def remaining():
        return BUDGET_S - (time.perf_counter() - t_start)

    tmp = tempfile.mkdtemp(prefix="bench_g500_")
    try:
        graph_path = os.path.join(tmp, "graph.npz")
        k1_info = None
        # BENCH_K1=device runs the distributed kernel1_device pipeline in a
        # dedicated process (k1_device_child). The default stays on the
        # host kernel 1: the route/dedup program did not compile within
        # 14 min at scale >= 17 in rounds 4-5, on a machine that is gone
        # (PERF_NOTES_r4; not re-measured).
        if os.environ.get("BENCH_K1", "host") == "device":
            # distributed kernel 1 in its own process (see k1_device_child)
            env = dict(os.environ)
            env["BENCH_K1_CHILD"] = "1"
            env["BENCH_GRAPH_NPZ"] = graph_path
            try:
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__)],
                    capture_output=True, text=True, env=env,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                    timeout=float(os.environ.get("BENCH_CHILD_TIMEOUT", "1800")),
                )
                k1_info = json.loads(
                    (r.stdout.strip().splitlines() or ["{}"])[-1]
                )
            except (subprocess.TimeoutExpired, json.JSONDecodeError):
                k1_info = None
        if k1_info and os.path.exists(graph_path):
            construction_s = k1_info["construction_s"]
        else:
            # host kernel 1 (and say so in the artifact)
            k1_info = {"fallback": "host numpy kernel 1"}
            construction_s = build_graph_npz(graph_path)
        # search-structure assembly (ELL buckets + CSC companion), ONCE,
        # in the parent — part of kernel 1 (OptimizeForGraph500 role),
        # counted into construction_s; children only upload.
        structures_s = augment_npz_with_structures(graph_path)
        construction_s += structures_s
        k1_info["structures_s"] = round(structures_s, 2)

        def run_child(extra_env):
            env = dict(os.environ)
            env["BENCH_GRAPH_NPZ"] = graph_path
            env.update(extra_env)
            try:
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__)],
                    capture_output=True, text=True, env=env,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                    timeout=float(os.environ.get("BENCH_CHILD_TIMEOUT", "1800")),
                )
                line = (r.stdout.strip().splitlines() or [""])[-1]
                stderr_tail = (r.stderr.strip().splitlines() or ["no output"])[-1]
            except subprocess.TimeoutExpired:
                line, stderr_tail = "", "child timeout"
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                return {"mteps": 0.0, "error": stderr_tail}

        runs = [
            run_child({"BENCH_CHILD": "1"}) for _ in range(max(REPEATS, 1))
        ]
        # REPEAT REPLACEMENT (predeclared): exactly one extra repeat if
        # any FAILED; the original stays in "runs".
        if any(r.get("mteps", 0) <= 0 for r in runs):
            rerun = run_child({"BENCH_CHILD": "1"})
            rerun["replacement"] = True
            runs.append(rerun)

        seq_runs = []
        # line 1: complete official record before any sequential root
        official = emit(runs, seq_runs, construction_s, k1_info, t_start)

        # UNTIMED WARMUP CHILD (predeclared protocol step): the first
        # process to compile the bfs_single program pays the compile +
        # persistent-cache write inside its own wall; one throwaway
        # child populates the cache so every TIMED root runs
        # warm. Its stats are recorded as diagnostics, never in the
        # statistic.
        est = 240.0  # first-child guess: cold compile + upload + drain
        if SEQ_ROOTS > 0 and remaining() > est:
            t0 = time.perf_counter()
            warm = run_child({"BENCH_SEQ_ROOT_IDX": "0"})
            est = time.perf_counter() - t0
            k1_info["seq_warmup_child"] = {
                "mteps": warm.get("mteps"),
                "warmup_s": warm.get("warmup_s"),
                "wall_s": round(est, 1),
                "obs_jsonl": warm.get("obs_jsonl"),
            }
            est = max(est * 0.7, 45.0)  # timed children run warm
        for i in range(min(SEQ_ROOTS, NROOTS)):
            if remaining() < est * 1.3 + 15:
                break
            t0 = time.perf_counter()
            seq_runs.append(
                run_child({
                    "BENCH_SEQ_ROOT_IDX": str(i),
                    "BENCH_SEQ_VALIDATE_THIS": "1" if i == 0 else "0",
                })
            )
            est = time.perf_counter() - t0
            official = emit(
                runs, seq_runs, construction_s, k1_info, t_start
            )
        if os.environ.get("BENCH_OBS") == "1":
            # merge the children's per-process telemetry sidecars into one
            # trace (the multihost aggregation path, host-side) and
            # re-emit the official line referencing it
            from combblas_tpu import obs

            # every obs-wired child: batch runs, seq roots, the k1 device
            # child (k1_info IS its JSON line), and the untimed warmup
            sources = runs + seq_runs + [
                k1_info, k1_info.get("seq_warmup_child") or {},
            ]
            sidecars = [
                r["obs_jsonl"] for r in sources
                if r.get("obs_jsonl") and os.path.exists(r["obs_jsonl"])
            ]
            if sidecars:
                merged_path = os.environ.get(
                    "BENCH_OBS_OUT", "obs_trace.jsonl"
                )
                try:
                    agg = obs.merge_jsonl_files(sidecars, merged_path)
                    k1_info["obs"] = {
                        "merged_jsonl": merged_path,
                        "children": len(sidecars),
                        "counters": agg["counters"],
                    }
                except Exception as e:
                    k1_info["obs"] = {"error": str(e)}
                official = emit(
                    runs, seq_runs, construction_s, k1_info, t_start
                )
        if not seq_runs:
            # never leave the artifact without the final (identical) line
            official = emit(
                runs, seq_runs, construction_s, k1_info, t_start
            )
        # FINAL LINE CONTRACT: the compact headline summary is the last
        # thing on stdout, plus BENCH_SUMMARY.json; its rc (non-zero
        # when nothing was measured, or not on the platform asked for)
        # is the exit code.
        return emit_summary(official)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _is_child_mode() -> bool:
    return any(
        os.environ.get(k)
        for k in ("BENCH_CHILD", "BENCH_K1_CHILD", "BENCH_SEQ_ROOT_IDX")
    )


if __name__ == "__main__":
    if _is_child_mode():
        main()  # children speak the one-JSON-line protocol, no summary
    else:
        try:
            rc = main()
        except BaseException as e:  # noqa: BLE001 — headline must survive
            # the final-line contract holds even on a crash: a summary
            # with rc=1 and the error as the warning, then re-raise so
            # the exit code and stderr traceback are unchanged
            if not isinstance(e, SystemExit) or (e.code or 0) != 0:
                emit_summary(
                    {"value": 0.0, "warning": f"{type(e).__name__}: {e}"},
                    rc=1,
                )
            raise
        sys.exit(rc)
