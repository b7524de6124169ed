"""Library call that counts over the whole graph, back to back:
``entry(A)`` on an ``SpParMat`` of the configuration's graph, with no
root and no argument but the matrix, one job in flight, each job closed
by the host's read of its count.  No front door, scheduler, engine
readback or scatter.  ``library_job.py``'s loop with another operand
(the matrix is uploaded here, once, at set-up:
``SpParMat.from_global_coo`` from the deployment's COO, unit values),
another answer (three integers, not ``[n]`` labels) and another
reference.  Jobs start until the window ends; only whole jobs count (a
job the window's end falls into is run to its end).

``mteps`` is the median over the jobs of the graph's undirected input
edges over one job's wall from launch to the host's count (LDBC
Graphalytics' edges per second of processing time, as the CC cell's).
It is not reported over fewer than three whole jobs.

A job reads nothing ``--seed`` draws: the graph is the configuration's
and every job starts from the stored edge list.  The seed picks which
jobs' triples are held to the reference (``tcref.TCReference.
check_count``: the first, the last and ``check.sampled`` others); every
other job's triple must equal the first's, so every job's count is the
reference's.  Integers: the limit is equality.

Mix parameters: ``entry`` (the program's entry point, as
``module:attr``: ``(A) -> (triangles, pairs, edges)``), ``check``
(``sampled``), ``trace``.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import serving, tccost, tcref
from chipbench.spec import resolve

#: whole jobs a window must hold for a median to be reported
LEAST_JOBS = 3


def check_jobs(ref: tcref.TCReference, answers: list, picks: list[int],
               ) -> list[str]:
    """``answers``: ``(triangles, pairs, edges)`` of every job, in
    order; ``picks``: the jobs held to the reference
    (``library_job.checked_jobs``)."""
    problems = []
    for k in picks:
        bad = ref.check_count(*answers[k])
        if bad:
            problems.append(f"job {k}: {bad}")
    for k, triple in enumerate(answers[1:], 1):
        if tuple(triple) != tuple(answers[0]):
            problems.append(
                f"job {k}: (triangles, pairs, edges) = {tuple(triple)}, "
                f"the first job's are {tuple(answers[0])}")
    return problems


def run(job) -> dict:
    mix = job.mix
    try:  # before the graph is loaded: a program without the entry
        fn = resolve(mix["entry"])
    except (ImportError, AttributeError) as e:
        raise SystemExit(
            f"chipbench: the program has no {mix['entry']!r} ({e}): the "
            "cell needs the triangle count's entry that returns its two "
            "counts"
        ) from e
    checked_jobs = job.spec.load_module("drivers", "library_job").checked_jobs
    dep = job.deploy()
    n = dep.n

    from combblas_tpu.parallel.spmat import SpParMat

    t0 = time.perf_counter()
    A = SpParMat.from_global_coo(
        dep.grid, dep.rows, dep.cols,
        np.ones(len(dep.rows), np.float32), n, n)
    A.rows.block_until_ready()
    serving.log(f"SpParMat of {len(dep.rows)} nonzeros uploaded in "
                f"{time.perf_counter() - t0:.1f} s")

    def one():
        """Launch a job; the host's read of its count closes it."""
        return tuple(int(v) for v in fn(A))

    # warm-up: one untimed job (compiles, or fetches the program from the
    # persistent cache)
    t0 = time.perf_counter()
    one()
    warmup_s = time.perf_counter() - t0
    serving.log(f"warm-up job: {warmup_s:.1f} s")

    c0 = job.compiles.count
    spans, walls, answers = [], [], []
    t_first = time.perf_counter()
    t_end = t_first + job.seconds
    if job.tracer:
        job.tracer.begin(t_first)
    while time.perf_counter() < t_end:
        w0, t0 = time.time(), time.perf_counter()
        answers.append(one())
        t1 = time.perf_counter()
        spans.append(("job", w0, w0 + (t1 - t0)))
        walls.append(t1 - t0)
    compiles = job.compiles.count - c0
    reduced, offset = job.tracer.finish() if job.tracer else (None, None)

    # checks, outside the window
    t0 = time.perf_counter()
    ref = tcref.TCReference(n, dep.rows, dep.cols)
    picks = checked_jobs(job.seed, len(answers), int(mix["check"]["sampled"]))
    problems = check_jobs(ref, answers, picks)
    triangles, pairs, edges = answers[0]
    serving.log(
        f"tc: the reference counts {ref.triangles} triangles over "
        f"{ref.edges} undirected edges of {n} vertices (largest "
        f"out-degree under the order {ref.max_out_degree}); checked jobs "
        f"{picks} of {len(answers)} against it (limit: equality) in "
        f"{time.perf_counter() - t0:.1f} s")
    serving.log(
        f"tc: the first job {triangles} triangles, {pairs} pairs walked "
        f"for {edges} edges; seconds by job: "
        + " ".join(f"{w:.3f}" for w in walls[:64])
        + (" ..." if len(walls) > 64 else ""))
    mteps = None
    if len(walls) >= LEAST_JOBS:
        mteps = float(np.median(ref.edges / np.asarray(walls) / 1e6))
    else:
        problems.append(
            f"{len(walls)} whole jobs in the window: no median over fewer "
            f"than {LEAST_JOBS}")
    ctx = {
        "load_s": dep.load_s, "load_how": dep.how, "warmup_s": warmup_s,
        "trace": reduced, "trace_offset": offset, "host_spans": spans,
        "job_walls": walls,
        "least_bytes": tccost.tc_job_least_bytes(n, len(dep.rows)),
        "gathered_bytes": tccost.gathered_bytes(pairs, n),
    }
    return {
        "attempted": len(answers),
        "failed": 0,
        "problems": problems,
        "compiles_in_window": compiles,
        "t_first_send": t_first,
        "values": {"mteps": mteps},
        "ctx": ctx,
    }
