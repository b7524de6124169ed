"""Library call on a whole bipartite pattern, back to back: ``entry(M)``
with no argument but the loaded operand, one job in flight, every job
from the empty matching, each closed by the readback of both mate
vectors (inside the entry: it returns host arrays).  No front door,
scheduler, engine readback or scatter.  Jobs start until the window
ends; only whole jobs count (a job the window's end falls into is run to
its end).

``mteps`` is the median over the jobs of the pattern's stored nonzeros
(the bipartite graph's edges) over one job's wall from launch to the
mates' readback.

A job reads nothing ``--seed`` draws: the pattern is the configuration's
and every job starts from the empty matching.  The seed picks which
jobs' answers are held to the reference on every entry
(``mcmref.McmReference.check``: the first, the last and ``check.sampled``
others); every other job's cardinality, phases and mates must equal the
first's.

Mix parameters: ``entry`` (the program's entry point, as ``module:attr``:
``(M) -> (mate_row, mate_col, cardinality, phases, ...)``), ``check``
(``sampled``), ``trace``.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import mcmcost, mcmdeploy, mcmref, serving
from chipbench.spec import resolve


def checked_jobs(seed: int, jobs: int, sampled: int) -> list[int]:
    """The jobs held to the reference: the first, the last and
    ``sampled`` of those between, drawn from ``seed``."""
    between = np.arange(1, jobs - 1)
    rng = np.random.default_rng([seed, 0x3C3])
    picks = rng.choice(between, min(sampled, len(between)), replace=False)
    return sorted({0, jobs - 1} | set(picks.tolist()))


def check_jobs(ref: mcmref.McmReference, answers: list, picks: list[int],
               ) -> list[str]:
    """``answers``: ``(mate_row, mate_col, cardinality, phases)`` of
    every job, in order; ``picks``: the jobs held to the reference
    (``checked_jobs``)."""
    problems = []
    for k in picks:
        mate_row, mate_col, cardinality, _ = answers[k]
        bad = ref.check(mate_row, mate_col)
        if bad is None and cardinality != ref.cardinality:
            bad = (f"says its cardinality is {cardinality}, its mates "
                   f"hold {ref.cardinality}")
        if bad:
            problems.append(f"job {k}: {bad}")
    first = answers[0]
    for k, (mate_row, mate_col, cardinality, phases) in enumerate(
            answers[1:], 1):
        if not (np.array_equal(mate_row, first[0])
                and np.array_equal(mate_col, first[1])):
            problems.append(
                f"job {k}: {int((mate_row != first[0]).sum())} rows' "
                "mates are not the first job's")
        if (cardinality, phases) != first[2:]:
            problems.append(
                f"job {k}: cardinality {cardinality} in {phases} phases, "
                f"the first job's was {first[2]} in {first[3]}")
    return problems


def run(job) -> dict:
    mix = job.mix
    try:  # before the pattern is loaded: a program without the entry
        fn = resolve(mix["entry"])
    except (ImportError, AttributeError) as e:
        raise SystemExit(
            f"chipbench: the program has no {mix['entry']!r} ({e}): the "
            "cell needs the matching job's one entry"
        ) from e
    dep = mcmdeploy.deploy_bipartite(job.cfg)
    M, nnz = dep.M, len(dep.rows)

    def one():
        """Launch a job; the entry closes it with the readback."""
        out = fn(M)
        return (out.mate_row, out.mate_col, int(out.cardinality),
                int(out.phases))

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
    ref = mcmref.McmReference(dep.nr, dep.nc, dep.rows, dep.cols)
    picks = checked_jobs(job.seed, len(answers), int(mix["check"]["sampled"]))
    problems = check_jobs(ref, answers, picks)
    _, _, cardinality, phases = answers[0]
    serving.log(
        f"mcm: the maximum is {ref.cardinality} of {dep.nr} rows and "
        f"{dep.nc} columns with {nnz} nonzeros; checked jobs {picks} of "
        f"{len(answers)} against the reference (limits: equality) in "
        f"{time.perf_counter() - t0:.1f} s")
    rates = nnz / np.asarray(walls) / 1e6
    serving.log(
        f"mcm: every job cardinality {cardinality} in {phases} phases; "
        "seconds by job: " + " ".join(f"{w:.3f}" for w in walls[:64])
        + (" ..." if len(walls) > 64 else ""))
    ctx = {
        "load_s": dep.load_s, "load_how": dep.how, "warmup_s": warmup_s,
        "trace": reduced, "trace_offset": offset, "host_spans": spans,
        "job_walls": walls,
        "least_bytes": mcmcost.mcm_job_least_bytes(nnz, dep.nr, dep.nc),
    }
    return {
        "attempted": len(answers),
        "failed": 0,
        "problems": problems,
        "compiles_in_window": compiles,
        "t_first_send": t_first,
        "values": {"mteps": float(np.median(rates))},
        "ctx": ctx,
    }
