"""Library call that clusters the whole graph, back to back:
``entry(A, **job)`` on an ``SpParMat`` of the configuration's graph, one
clustering in flight, each job closed by the host's read of its digest.
No front door, scheduler, engine or ELL sweep.  ``library_product.py``'s
loop with another operand (a float-valued similarity graph that is NOT
R-MAT: ``chipbench/famgraph.py`` builds it here, from the
configuration's ``family_graph`` and ``graph_seed``, and its COO is kept
under ``.cache/<config>-<key>/`` as ``deploy.py`` keeps a snapshot's),
another answer (labels that STAY on the device and the digest that comes
back) and another reference (``chipbench/mclref.py``: float64, with
limits).  Jobs start until the window ends; only whole jobs count (a job
the window's end falls into is run to its end).

``mteps`` is the median over the jobs of the graph's undirected input
edges over one job's wall from launch to the host's digest.  It is not
reported over fewer than four whole jobs.

A job reads nothing ``--seed`` draws.  What decides ``correct``:

(a) every timed job's digest equals the first's;
(b) the first job's iteration count, cluster count and label
    fingerprint equal the reference's, and its chaos and stored entries
    an iteration lie within the configuration's ``limits``;
(c) ONE checked job after the window, the same entry with a hook that
    hands out the state after iterations 1, 2, 3 and the last one a
    dense tier ran: each is held to the reference's matrix of that
    iteration a column at a time (L1), over ``check.columns`` columns
    that ``--seed`` draws from the reference's kept pool.  Its digest
    must be the first job's too.

The reference is computed once a checkout (minutes of scipy at the
shipped scale) and kept beside the graph; like every cell's reference it
is made AFTER the window, so ``setup_s`` does not hold it.

Mix parameters: ``entry`` (``module:attr``: ``(A, **job) -> (labels,
digest)``), ``job`` (further keyword arguments; the published
parameters come from the configuration's ``mcl``), ``check``
(``columns``, ``pool``, ``iterations``), ``trace``.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

import numpy as np
import scipy.sparse as sp

from chipbench import famgraph, mclcost, mclref, serving
from chipbench.spec import resolve

#: whole jobs a window must hold for a median to be reported
LEAST_JOBS = 4
PARTS = ("iters", "chaos", "stored", "clusters", "fingerprint")


def same_digest(a: dict, b: dict) -> bool:
    return all(np.array_equal(a[k], b[k]) for k in PARTS)


def cache_key(cfg: dict) -> str:
    """Configuration, generator and reference: what the kept graph and
    reference are functions of."""
    h = hashlib.sha256()
    for path in (cfg["_file"], famgraph.__file__, mclref.__file__):
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def load_graph(cfg: dict, cache: str | None):
    """``(n, rows, cols, vals, how)``: from the kept COO, else built."""
    path = cache and os.path.join(cache, "graph.npz")
    if path and os.path.isfile(path):
        try:
            z = np.load(path)
            return int(z["n"]), z["rows"], z["cols"], z["vals"], "snapshot"
        except Exception as e:  # any load error: build instead
            serving.log(f"{path} unusable ({type(e).__name__}: {e})")
    n, rows, cols, vals, _ = famgraph.family_graph(
        int(cfg["scale"]), int(cfg["graph_seed"]), **cfg["family_graph"])
    if path:
        os.makedirs(cache, exist_ok=True)
        np.savez(path + ".tmp.npz", n=n, rows=rows, cols=cols, vals=vals)
        os.replace(path + ".tmp.npz", path)
    return n, rows, cols, vals, "built"


def pool_columns(n: int, graph_seed: int, size: int) -> np.ndarray:
    """The columns whose reference states are kept: ``--seed`` samples
    the checked job's columns from these."""
    rng = np.random.default_rng([graph_seed, 0x3C1])
    return np.sort(rng.choice(n, min(size, n), replace=False))


def sample_columns(seed: int, mix: dict, pool) -> np.ndarray:
    """The positions in the pool ``--seed`` draws for the checked job's
    states."""
    rng = np.random.default_rng([seed, 0xC01])
    return np.sort(rng.choice(
        len(pool), min(int(mix["check"]["columns"]), len(pool)),
        replace=False))


def load_reference(cfg, mix, cache, n, rows, cols, vals) -> dict:
    """``mclref.mcl_reference`` of the configuration, with the states
    after the first ``check.iterations`` iterations cut to the pool;
    from ``reference.npz`` where a run of this checkout left one."""
    check = mix["check"]
    pool = pool_columns(n, int(cfg["graph_seed"]), int(check["pool"]))
    path = cache and os.path.join(cache, "reference.npz")
    if path and os.path.isfile(path):
        try:
            z = np.load(path)
            ref = json.loads(str(z["scalars"]))
            ref["labels"] = z["labels"]
            ref["matrices"] = {
                int(k): sp.csc_matrix(
                    (z[f"d{k}"], z[f"i{k}"], z[f"p{k}"]),
                    shape=(n, len(pool)))
                for k in ref.pop("kept")}
            ref["pool"], ref["how"] = pool, "kept"
            return ref
        except Exception as e:
            serving.log(f"{path} unusable ({type(e).__name__}: {e})")
    t0 = time.perf_counter()
    ref = mclref.mcl_reference(
        n, rows, cols, vals, **{k: cfg["mcl"][k] for k in mclref.PARAMS},
        keep=range(1, int(check["iterations"]) + 1), columns=pool,
        log=serving.log)
    ref["seconds"] = time.perf_counter() - t0
    if path:
        os.makedirs(cache, exist_ok=True)
        scalars = {k: ref[k] for k in (
            "iters", "clusters", "chaos", "stored", "counts", "seconds")}
        scalars["kept"] = sorted(ref["matrices"])
        arrays = {"labels": ref["labels"], "scalars": json.dumps(scalars)}
        for k, mat in ref["matrices"].items():
            arrays.update({f"d{k}": mat.data, f"i{k}": mat.indices,
                           f"p{k}": mat.indptr})
        np.savez(path + ".tmp.npz", **arrays)
        os.replace(path + ".tmp.npz", path)
    ref["pool"], ref["how"] = pool, "computed"
    return ref


def checked_iterations(tiers, kept) -> list[int]:
    """1, 2, 3 and the last iteration a dense tier ran, of those the
    job ran and the reference kept."""
    dense = [k + 1 for k, t in enumerate(tiers) if t != "scan" and t != "esc"]
    want = {1, 2, 3} | ({dense[-1]} if dense else set())
    return sorted(k for k in want if k <= len(tiers) and k in kept)


def check_jobs(ref: dict, digests: list, checked: dict | None,
               limits: dict, n: int, columns) -> list[str]:
    """``digests``: every timed job's, in order; ``checked``: the
    checked job's ``{"digest", "states": {iteration: (rows, cols,
    vals)}}``; ``columns``: positions in the reference's pool."""
    problems = []
    bad = mclref.check_digest(ref, digests[0], limits)
    if bad:
        problems.append(f"job 0: {bad}")
    for k, d in enumerate(digests[1:], 1):
        if not same_digest(d, digests[0]):
            problems.append(f"job {k}: its digest is not the first job's")
    if checked is not None:
        if not same_digest(checked["digest"], digests[0]):
            problems.append("the checked job's digest is not the first job's")
        for it, got in sorted(checked["states"].items()):
            bad, worst, mean = mclref.check_matrix(
                n, got, ref["matrices"][it][:, columns],
                ref["pool"][columns], limits, f"after iteration {it}")
            serving.log(
                f"mcl: after iteration {it} the {len(columns)} sampled "
                f"columns lie at most {worst:.3g} and in the mean "
                f"{mean:.3g} (L1) from the reference's")
            if bad:
                problems.append(bad)
    return problems


def run(job) -> dict:
    mix, cfg = job.mix, job.cfg
    try:  # before the graph is built: a program without the entry
        fn = resolve(mix["entry"])
    except (ImportError, AttributeError) as e:
        raise SystemExit(
            f"chipbench: the program has no {mix['entry']!r} ({e}): the "
            "cell needs the clustering's job entry that returns the "
            "labels and a digest"
        ) from e
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat
    from combblas_tpu.serve import GraphEngine

    cache = os.path.join(
        job.spec.cache_dir(), f"{cfg['name']}-{cache_key(cfg)}")
    t0 = time.perf_counter()
    n, rows, cols, vals, how = load_graph(cfg, cache)
    edges = len(rows) // 2  # symmetrised, no loops: two nonzeros each
    grid = Grid.make(*cfg["grid"])
    # through the user entry point, as every one-chip configuration is
    # loaded (its spans are the boot's ``graph_ready_s`` and
    # ``upload_s``); the job reads the SpParMat, never the engine
    engine = GraphEngine.from_coo(
        grid, rows, cols, n, kinds=tuple(cfg["kinds"]))
    A = SpParMat.from_global_coo(grid, rows, cols, vals, n, n)
    A.rows.block_until_ready()
    load_s = time.perf_counter() - t0
    serving.log(f"family graph of {n} vertices and {edges} undirected "
                f"edges {how} and uploaded in {load_s:.1f} s")
    kw = dict({k: cfg["mcl"][k] for k in mclref.PARAMS + ("mode",)},
              **mix.get("job", {}))
    kept = [None]  # the one job's labels on the chip

    def one(**more):
        """Launch a job; the host's read of its digest closes it."""
        kept[0] = None
        kept[0], digest = fn(A, **kw, **more)
        return digest

    # warm-up: one untimed job (compiles, or fetches the programs from
    # the persistent cache)
    t0 = time.perf_counter()
    warm = one()
    warmup_s = time.perf_counter() - t0
    serving.log(f"warm-up job: {warmup_s:.1f} s, {warm['iters']} "
                f"iterations, tiers {' '.join(warm['tiers'])}")

    c0 = job.compiles.count
    spans, walls, digests = [], [], []
    t_first = time.perf_counter()
    t_end = t_first + job.seconds
    if job.tracer:
        job.tracer.begin(t_first)
    while time.perf_counter() < t_end:
        w0, t0 = time.time(), time.perf_counter()
        digests.append(one())
        t1 = time.perf_counter()
        spans.append(("job", w0, w0 + (t1 - t0)))
        walls.append(t1 - t0)
    compiles = job.compiles.count - c0
    reduced, offset = job.tracer.finish() if job.tracer else (None, None)

    # checks, outside the window
    t0 = time.perf_counter()
    ref = load_reference(cfg, mix, cache, n, rows, cols, vals)
    serving.log(
        f"mcl: the reference ({ref['how']}, {ref['seconds']:.1f} s of "
        f"scipy in float64): {ref['iters']} iterations, {ref['clusters']} "
        f"clusters; its first three expansions: "
        + "; ".join(json.dumps(c) for c in ref["counts"][:3]))
    first = digests[0]
    want = checked_iterations(first["tiers"], ref["matrices"])
    states, marks = {}, []

    def hook(it, tier, fetch):
        t = time.perf_counter()
        if it in want:
            states[it] = fetch()
        marks.append((t, time.perf_counter()))

    t_checked = time.perf_counter()
    checked = {"digest": one(hook=hook), "states": states}
    columns = sample_columns(job.seed, mix, ref["pool"])
    problems = check_jobs(
        ref, digests, checked, cfg["limits"], n, columns)
    by_iter = [b[0] - a for a, b in zip(
        [t_checked] + [mk[1] for mk in marks], marks)]
    exp = np.asarray(ref["chaos"][:first["iters"]])
    got = np.asarray(first["chaos"], np.float64)[:len(exp)]
    serving.log(
        f"mcl: the first job {first['iters']} iterations, "
        f"{first['clusters']} clusters, fingerprint "
        f"{first['fingerprint']}; its chaos lies at most "
        f"{np.max(np.abs(got - exp) / np.maximum(exp, 1e-30)):.3g} "
        "(relative) and "
        f"{np.max(np.abs(got - exp)):.3g} (absolute) from the "
        "reference's; stored entries at most "
        f"{np.max(np.abs(np.asarray(first['stored'][:len(exp)]) - np.asarray(ref['stored'][:len(exp)])) / np.asarray(ref['stored'][:len(exp)])):.3g} "
        f"(relative); checked in {time.perf_counter() - t0:.1f} s")
    serving.log(
        "mcl: the checked job's seconds by iteration (fetches left "
        "out): " + " ".join(
            f"{t}:{s:.3f}" for t, s in zip(first["tiers"], by_iter)))
    serving.log(
        "mcl: chaos by iteration: "
        + " ".join(f"{c:.6g}" for c in first["chaos"]))
    serving.log(
        "mcl: stored by iteration: "
        + " ".join(str(int(s)) for s in first["stored"])
        + "; seconds by job: " + " ".join(f"{w:.3f}" for w in walls[:64]))
    mteps = None
    if len(walls) >= LEAST_JOBS:
        mteps = float(np.median(edges / np.asarray(walls) / 1e6))
    else:
        problems.append(
            f"{len(walls)} whole jobs in the window: no median over fewer "
            f"than {LEAST_JOBS}")
    ctx = {
        "load_s": load_s, "load_how": how, "warmup_s": warmup_s,
        "trace": reduced, "trace_offset": offset, "host_spans": spans,
        "job_walls": walls, "engine": engine,
        "least_bytes": mclcost.mcl_job_least_bytes(
            len(rows) + n, ref["stored"]),
    }
    return {
        "attempted": len(digests),
        "failed": 0,
        "problems": problems,
        "compiles_in_window": compiles,
        "t_first_send": t_first,
        "values": {"mteps": mteps},
        "ctx": ctx,
    }
