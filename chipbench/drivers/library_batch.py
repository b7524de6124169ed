"""Library call, back to back: ``entry(E, roots)`` on ``width`` new roots
per batch, each batch closed by the ``[width]`` traversed-edge readback
of ``edges_entry``.  No front door, scheduler, engine readback or
scatter.  Batches start until the window ends; only whole batches count.

``mteps`` is the median over the batches of one batch's traversed edges
over its wall from launch to readback (Graph500's ``median_TEPS``, a batch
for a search).  A batch sweeps once per BFS level of its deepest root, so
one batch with a level more or fewer moves the aggregate of a window of
twelve by 1 % and the median not at all; the aggregate (all edges over
first launch to last readback) is kept for the ``mteps_aggregate`` reader.

Mix parameters: ``entry`` / ``edges_entry`` (the program's entry points,
as ``module:attr``), ``width``, ``check`` (``exact``, ``tree``: sampled
columns of the LAST batch), ``trace``.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import cost, graph, serving
from chipbench.spec import resolve


def run(job) -> dict:
    import jax

    from combblas_tpu.parallel.vec import DistMultiVec, DistVec

    mix = job.mix
    dep = job.deploy()
    fn, edges_fn = resolve(mix["entry"]), resolve(mix["edges_entry"])
    width, check = int(mix["width"]), mix["check"]
    E, n = dep.engine.E, dep.n
    deg_blocks = DistVec.from_global(
        dep.grid, dep.deg.astype(np.int32), align="row"
    ).blocks
    chunk = 1024  # most batches a window can hold: roots drawn at once
    roots = graph.draw_roots(dep.deg, job.seed, (chunk + 1) * width)

    def batch(k: int):
        """Launch batch ``k`` and close it with the readback."""
        r = jax.device_put(roots[k * width:(k + 1) * width])
        p, l, niter = fn(E, r)
        te = np.asarray(edges_fn(deg_blocks, p))  # the barrier
        return p, l, int(niter), te

    # warm-up: one untimed batch on roots of its own (compiles, or
    # fetches the program from the persistent cache)
    t0 = time.perf_counter()
    batch(chunk)
    warmup_s = time.perf_counter() - t0
    serving.log(f"warm-up batch: {warmup_s:.1f} s")

    c0 = job.compiles.count
    spans, edges, walls, levels_run = [], [], [], []
    t_first = time.perf_counter()
    t_end = t_first + job.seconds
    if job.tracer:
        job.tracer.begin(t_first)
    k = 0
    last = None
    while time.perf_counter() < t_end and k < chunk:
        w0, t0 = time.time(), time.perf_counter()
        last = None  # free the previous batch's [n, width] results
        p, l, niter, te = batch(k)
        t1 = time.perf_counter()
        spans.append(("batch", w0, w0 + (t1 - t0)))
        edges.append(int(te.astype(np.int64).sum()))
        walls.append(t1 - t0)
        levels_run.append(niter)
        last = (k, p, l, te)
        k += 1
    t_last = time.perf_counter()
    compiles = job.compiles.count - c0
    reduced, offset = job.tracer.finish() if job.tracer else (None, None)

    # checks, outside the window: sampled columns of the last batch
    t0 = time.perf_counter()
    problems = []
    kb, p, l, te = last
    rng = np.random.default_rng([job.seed, 0x5A3B])
    cols = np.sort(rng.choice(width, int(check["tree"]), replace=False))
    pick = lambda mv: DistMultiVec(
        blocks=mv.blocks[:, :, cols], length=n, align="row", grid=dep.grid
    ).to_global()
    lv, pa = np.asarray(pick(l)), np.asarray(pick(p))
    del p, l, last
    ref = dep.reference()
    for j, c in enumerate(cols):
        root = int(roots[kb * width + c])
        if j < int(check["exact"]):
            bad = ref.check_exact(lv[:, j], root)
            if bad:
                problems.append(f"batch {kb} column {c}: {bad}")
        bad = ref.check_tree(lv[:, j], pa[:, j], root)
        if bad:
            problems.append(f"batch {kb} column {c}: {bad}")
        want = ref.traversed_edges(lv[:, j])
        if int(te[c]) != want:
            problems.append(
                f"batch {kb} column {c}: traversed edges {int(te[c])}, "
                f"reference says {want}"
            )
    serving.log(f"checked {len(cols)} columns of batch {kb} in "
                f"{time.perf_counter() - t0:.1f} s; {k} batches")

    rates = np.asarray(edges, np.float64) / np.asarray(walls) / 1e6
    serving.log(
        "levels run by batch: " + " ".join(str(v) for v in levels_run)
        + "; Medges/s by batch: " + " ".join(f"{r:.1f}" for r in rates)
    )
    ctx = {
        "load_s": dep.load_s, "load_how": dep.how, "warmup_s": warmup_s,
        "trace": reduced, "trace_offset": offset, "host_spans": spans,
        "mteps_aggregate": sum(edges) / (t_last - t_first) / 1e6,
        "least_bytes": cost.bfs_batch_least_bytes(
            n, cost.ell_slots(E), width, int(np.median(levels_run))
        ),
    }
    return {
        "attempted": k * width,
        "failed": 0,
        "problems": problems,
        "compiles_in_window": compiles,
        "t_first_send": t_first,
        "values": {"mteps": float(np.median(rates))},
        "ctx": ctx,
    }
