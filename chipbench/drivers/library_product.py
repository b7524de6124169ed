"""Library call that multiplies the whole matrix by itself, back to back:
``entry(sr, A, A)`` on an ``SpParMat`` of the configuration's graph, one
product in flight, each job closed by the host's read of its digest.  No
front door, scheduler, engine readback or scatter.  ``library_count.py``'s
loop with another operand pair (one matrix, uploaded here, once, at
set-up: ``SpParMat.from_global_coo`` from the deployment's COO, unit
values; both operands are it), another answer (a matrix that STAYS on
the device and the digest that comes back) and another reference.  Jobs
start until the window ends; only whole jobs count (a job the window's
end falls into is run to its end).  The job before is dropped before the
next starts: one C lives on the chip at a time.

``mteps`` is the median over the jobs of the graph's undirected input
edges over one job's wall from launch to the host's digest (edges per
second of processing time, as the CC and TC cells').  It is not reported
over fewer than four whole jobs.

A job reads nothing ``--seed`` draws: the matrix is the configuration's
and every job starts from the stored tuples.  The seed picks which jobs'
digests are held to the reference (``sqref.SQReference.check_digest``:
the first, the last and ``check.sampled`` others); every other job's
digest must equal the first's, so every job's digest is the reference's.
The LAST job's C is read back whole after the window and held to the
reference entry for entry (``check_entries``).  Integers: the limit of
both is equality.

Mix parameters: ``entry`` (the program's entry point, as ``module:attr``:
``(sr, A, B, **job) -> (C, digest)``), ``semiring`` (``module:attr``),
``job`` (keyword arguments: the tier, the backend and the stage product's
input pass the chip runs, passed on so that a rehearsal runs them too),
``check`` (``sampled``), ``trace``.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import serving, sqcost, sqref
from chipbench.spec import resolve

#: whole jobs a window must hold for a median to be reported
LEAST_JOBS = 4
PARTS = ("nnz", "sum", "counts", "sums", "prints")


def same_digest(a: dict, b: dict) -> bool:
    return all(np.array_equal(a[k], b[k]) for k in PARTS)


def check_jobs(ref: sqref.SQReference, digests: list, picks: list[int],
               last: tuple | None) -> list[str]:
    """``digests``: every job's, in order; ``picks``: the jobs held to
    the reference (``library_job.checked_jobs``); ``last``: the stored
    ``(rows, cols, vals)`` of the last job's C, read back whole."""
    problems = []
    for k in picks:
        bad = ref.check_digest(digests[k])
        if bad:
            problems.append(f"job {k}: {bad}")
    for k, d in enumerate(digests[1:], 1):
        if not same_digest(d, digests[0]):
            problems.append(f"job {k}: its digest is not the first job's")
    if last is not None:
        bad = ref.check_entries(*last)
        if bad:
            problems.append(f"the last job's C: {bad}")
    return problems


def stored(C) -> tuple:
    """The stored tuples of a one-tile ``SpParMat``, on the host."""
    rows = np.asarray(C.rows)[0, 0]
    keep = rows < C.nrows
    return (rows[keep], np.asarray(C.cols)[0, 0][keep],
            np.asarray(C.vals)[0, 0][keep])


def run(job) -> dict:
    mix = job.mix
    try:  # before the graph is loaded: a program without the entry
        fn, sr = resolve(mix["entry"]), resolve(mix["semiring"])
    except (ImportError, AttributeError) as e:
        raise SystemExit(
            f"chipbench: the program has no {mix['entry']!r} ({e}): the "
            "cell needs the sparse product's job entry that returns C "
            "and its digest"
        ) from e
    checked_jobs = job.spec.load_module("drivers", "library_job").checked_jobs
    dep = job.deploy()
    n = dep.n
    edges = len(dep.rows) // 2  # symmetrised, no loops: two nonzeros each

    from combblas_tpu.parallel.spmat import SpParMat

    t0 = time.perf_counter()
    A = SpParMat.from_global_coo(
        dep.grid, dep.rows, dep.cols,
        np.ones(len(dep.rows), np.float32), n, n)
    A.rows.block_until_ready()
    serving.log(f"SpParMat of {len(dep.rows)} nonzeros uploaded in "
                f"{time.perf_counter() - t0:.1f} s")
    kept = [None]  # the one C on the chip

    def one():
        """Launch a job; the host's read of its digest closes it."""
        kept[0] = None
        kept[0], digest = fn(sr, A, A, **mix["job"])
        return digest

    # warm-up: one untimed job (compiles, or fetches the programs from
    # the persistent cache)
    t0 = time.perf_counter()
    warm = one()
    warmup_s = time.perf_counter() - t0
    serving.log(f"warm-up job: {warmup_s:.1f} s, tier {warm.get('tier')} "
                f"under {warm.get('backend')}")

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
    last = stored(kept[0])
    kept[0] = None
    ref = sqref.SQReference(n, dep.rows, dep.cols)
    picks = checked_jobs(job.seed, len(digests), int(mix["check"]["sampled"]))
    problems = check_jobs(ref, digests, picks, last)
    serving.log(
        f"sq: the reference's C has {ref.digest['nnz']} entries of sum "
        f"{ref.digest['sum']} (the largest {ref.largest}) from "
        f"{ref.products} products of {ref.nnz_a} nonzeros, {edges} "
        f"undirected edges of {n} vertices; checked the digests of jobs "
        f"{picks} of {len(digests)} and the last job's {len(last[0])} "
        "stored entries against it (limit: equality) in "
        f"{time.perf_counter() - t0:.1f} s")
    serving.log(
        f"sq: the first job {digests[0]['nnz']} entries of sum "
        f"{digests[0]['sum']}; seconds by job: "
        + " ".join(f"{w:.3f}" for w in walls[:64])
        + (" ..." if len(walls) > 64 else ""))
    mteps = None
    if len(walls) >= LEAST_JOBS:
        mteps = float(np.median(edges / np.asarray(walls) / 1e6))
    else:
        problems.append(
            f"{len(walls)} whole jobs in the window: no median over fewer "
            f"than {LEAST_JOBS}")
    ctx = {
        "load_s": dep.load_s, "load_how": dep.how, "warmup_s": warmup_s,
        "trace": reduced, "trace_offset": offset, "host_spans": spans,
        "job_walls": walls,
        "least_bytes": sqcost.sq_job_least_bytes(
            ref.nnz_a, ref.digest["nnz"]),
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
