"""Library call on the whole graph, back to back: ``entry(E)`` with no
root and no argument but the loaded matrix, one job in flight, each job
closed by the readback of its ``[n]`` labels and its two counts.  No
front door, scheduler, engine readback or scatter.  Jobs start until the
window ends; only whole jobs count (a job the window's end falls into is
run to its end).

``mteps`` is the median over the jobs of the graph's undirected input
edges over one job's wall from launch to readback (LDBC Graphalytics'
edges per second of processing time; a job for a search, as
``library_batch`` takes a batch for a search).

A job reads nothing ``--seed`` draws: the graph is the configuration's
and every job starts from ``f = iota``.  The seed picks which jobs'
answers are held to the reference on all ``n`` entries
(``ccref.CCReference.check_labels``: the first, the last and
``check.sampled`` others); every other job's labels, rounds and jumps
must equal the first's.

Mix parameters: ``entry`` (the program's entry point, as
``module:attr``: ``(E) -> (labels, rounds, jumps)``), ``check``
(``sampled``), ``trace``.  Traced, the kind's six readings
(``chipbench/layers/cc_*.py``) are logged: the result line cannot carry
them until ``BENCHMARK.json`` lists them, which takes a ``benchmark`` PR
(``PERF.md`` section 7).
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import cccost, ccref, cost, serving
from chipbench.spec import resolve

LAYERS = ("cc_device_ms", "cc_round_ms", "cc_rounds", "cc_spmv_share",
          "cc_hook_share", "cc_hbm_share")


def checked_jobs(seed: int, jobs: int, sampled: int) -> list[int]:
    """The jobs held to the reference: the first, the last and
    ``sampled`` of those between, drawn from ``seed``."""
    between = np.arange(1, jobs - 1)
    rng = np.random.default_rng([seed, 0x5A3B])
    picks = rng.choice(between, min(sampled, len(between)), replace=False)
    return sorted({0, jobs - 1} | set(picks.tolist()))


def check_jobs(ref: ccref.CCReference, answers: list, picks: list[int],
               ) -> list[str]:
    """``answers``: ``(labels, rounds, jumps)`` of every job, in order;
    ``picks``: the jobs held to the reference (``checked_jobs``)."""
    problems = []
    for k in picks:
        bad = ref.check_labels(answers[k][0])
        if bad:
            problems.append(f"job {k}: {bad}")
    first = answers[0]
    for k, (labels, rounds, jumps) in enumerate(answers[1:], 1):
        if not np.array_equal(labels, first[0]):
            problems.append(
                f"job {k}: {int((labels != first[0]).sum())} labels are "
                "not the first job's")
        if (rounds, jumps) != first[1:]:
            problems.append(
                f"job {k}: {rounds} rounds and {jumps} jumps, the first "
                f"job ran {first[1]} and {first[2]}")
    return problems


def log_layers(job, ctx: dict) -> None:
    """Each of the kind's readers on this run's ``ctx``, logged; a reader
    that finds nothing (no device plane, no counter) says so."""
    ctx = dict(ctx, device=job.device, cfg=job.cfg)
    for name in LAYERS:
        value = job.spec.load_module("layers", name).read(ctx)
        serving.log(f"layer {name}: " + (
            "nothing to read" if value is None else repr(float(value))))


def run(job) -> dict:
    mix = job.mix
    try:  # before the graph is loaded: a program without the entry
        fn = resolve(mix["entry"])
    except (ImportError, AttributeError) as e:
        raise SystemExit(
            f"chipbench: the program has no {mix['entry']!r} ({e}): the "
            "cell needs FastSV's entry that returns its two counts"
        ) from e
    dep = job.deploy()
    E, n = dep.engine.E, dep.n
    edges = len(dep.rows) // 2  # symmetrised, no loops: two nonzeros each

    def one():
        """Launch a job and close it with the readback."""
        labels, rounds, jumps = fn(E)
        return labels.to_global(), int(rounds), int(jumps)  # the barrier

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
    ref = ccref.CCReference(n, dep.rows, dep.cols)
    picks = checked_jobs(job.seed, len(answers), int(mix["check"]["sampled"]))
    problems = check_jobs(ref, answers, picks)
    _, rounds, jumps = answers[0]
    serving.log(
        f"cc: {ref.components} components, the largest {ref.largest} of "
        f"{n} vertices; checked jobs {picks} of {len(answers)} against the "
        "reference on all entries (limit: equality) in "
        f"{time.perf_counter() - t0:.1f} s")
    rates = edges / np.asarray(walls) / 1e6
    serving.log(
        f"cc: every job {rounds} rounds and {jumps} jumps; seconds by "
        "job: " + " ".join(f"{w:.3f}" for w in walls[:64])
        + (" ..." if len(walls) > 64 else ""))
    ctx = {
        "load_s": dep.load_s, "load_how": dep.how, "warmup_s": warmup_s,
        "trace": reduced, "trace_offset": offset, "host_spans": spans,
        "job_walls": walls,
        "rounds": [a[1] for a in answers], "jumps": [a[2] for a in answers],
        "least_bytes": cccost.cc_job_least_bytes(
            cost.ell_slots(E), n, rounds, jumps),
    }
    if job.trace:
        log_layers(job, ctx)
    return {
        "attempted": len(answers),
        "failed": 0,
        "problems": problems,
        "compiles_in_window": compiles,
        "t_first_send": t_first,
        "values": {"mteps": float(np.median(rates))},
        "ctx": ctx,
    }
