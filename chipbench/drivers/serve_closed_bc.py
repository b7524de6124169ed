"""``serve_closed``'s loop for GAP's BC kernel: a kind whose answer is
one root's dependency vector (``scores``) and whose client asks four
roots at a time.

``in_flight`` requests always outstanding, submitted one GAP trial
(``trial`` roots, ``Server.submit_many``) at a time: whenever a trial's
worth has completed and the window is open, the next trial goes in; then
the rest drain.  Every answer gets ``bcref.check_answer`` (O(n), no
search).  A seeded sample, uniform over ALL the requests the run sent
(drawn as they are submitted: nobody knows beforehand how many a window
holds), is kept and checked outside the window against
``chipbench/bcref.py``: whole trials (``check.exact`` answers in all, in
request order) entry by entry against float64 Brandes and as a sum over
the trial, and ``check.sum`` answers against the sum rule and the depth
the batch reported.  An answer without ``scores`` or without
``batch_niter`` (a program from before the plan returned its depth) ends
the run at once, non-zero, with a message.

Mix parameters: ``serve_closed``'s (``kind``, ``in_flight``, ``drain_s``,
``check``: ``exact``, ``sum``; ``trace``) and ``trial``, the roots of one
``submit_many`` (GAP's four), which shapes the traffic and is no
parameter of the check.  Traced, ``ctx["bc_cost"]``
holds what ``bccost.bc_batch_least_bytes`` needs besides the sweeps, and
the kind's six readings (``chipbench/layers/bc_*.py``) are logged: the
result line cannot carry them until ``BENCHMARK.json`` lists them, which
takes a ``benchmark`` PR (``PERF.md`` section 7).
"""

from __future__ import annotations

import queue
import time

import numpy as np

from chipbench import bcref, cost, graph, loadgen, serving


LAYERS = ("bc_device_ms", "bc_forward_ms", "bc_backward_ms",
          "bc_sweeps", "bc_gather_share", "bc_hbm_share")


class BCSampler:
    """A seeded sample, uniform over the requests submitted so far, by
    reservoirs filled at submission: ``exact // trial`` whole trials
    (``trial`` consecutive requests, as one ``submit_many`` sent them)
    for the exact checks and ``count`` requests for the sum rule.  Every
    other answer gets the O(n) checks and is dropped; so is a kept answer
    whose place in the sample a later request takes."""

    def __init__(self, seed: int, trial: int, exact: int, count: int, deg):
        if exact % trial:
            raise SystemExit(
                f"chipbench: check.exact = {exact} is no whole number of "
                f"trials of {trial}")
        self.rng = np.random.default_rng([seed, 0x5A3B])
        self.trial = trial
        self.n_trials, self.n_sum = exact // trial, count
        self.trials = []  # first request of each sampled trial
        self.sum = []  # sampled requests
        self.deg = deg
        self.kept = {}
        self.problems = []

    def _offer(self, pool: list, room: int, seen: int, item: int) -> None:
        """Algorithm R: ``item`` is the ``seen``-th (from 0) offered."""
        if len(pool) < room:
            pool.append(item)
        elif (at := int(self.rng.integers(seen + 1))) < room:
            pool[at] = item

    def wants(self, idx: int) -> bool:
        return idx in self.sum or idx - idx % self.trial in self.trials

    def submitted(self, first: int) -> None:
        """One trial went in: requests ``first .. first + trial - 1``."""
        self._offer(self.trials, self.n_trials, first // self.trial, first)
        for idx in range(first, first + self.trial):
            self._offer(self.sum, self.n_sum, idx, idx)
        self.kept = {i: a for i, a in self.kept.items() if self.wants(i)}

    @property
    def exact(self) -> list:
        """The sampled trials' requests, in request order."""
        return [i for first in sorted(self.trials)
                for i in range(first, first + self.trial)]

    def take(self, idx: int, root: int, result: dict) -> None:
        bad = bcref.check_answer(result["scores"], root, self.deg)
        if bad:
            self.problems.append(f"request {idx}: {bad}")
        if self.wants(idx):
            # a copy: the lane is a view that pins its batch's [n, W]
            self.kept[idx] = (root, np.array(result["scores"]),
                              int(result["batch_niter"]))


def require_scores(result: dict) -> None:
    """End the run where the program's answer is not this kind's."""
    if "scores" not in result or "batch_niter" not in result:
        raise SystemExit(
            f"chipbench: the program's bc answer holds {sorted(result)}: "
            "the BC cell needs 'scores' and 'batch_niter' (the depth the "
            "served plan returns)"
        )


def check_sample(ref: bcref.BCReference, sampler: BCSampler) -> list[str]:
    problems = list(sampler.problems)
    kept = sampler.kept
    worst = {"exact": 0.0, "trial": 0.0, "sum": 0.0}
    missing = [i for i in sampler.exact if i not in kept]
    if missing:
        problems.append(f"{len(missing)} of the sampled trials' "
                        f"{len(sampler.exact)} answers did not complete")
    for first in sorted(sampler.trials):
        trial = [i for i in range(first, first + sampler.trial)
                 if i in kept]
        want_total, got_total = 0.0, 0.0
        for idx in trial:
            root, scores, _ = kept[idx]
            want = ref.dependencies(root)
            bad = ref.check_exact(scores, root, want)
            if bad:
                problems.append(f"request {idx}: {bad}")
            worst["exact"] = max(worst["exact"], ref.worst(scores, want))
            want_total = want_total + want
            got_total = got_total + scores.astype(np.float64)
        if trial:
            bad = ref.check_trial(got_total, [kept[i][0] for i in trial],
                                  want_total)
            if bad:
                problems.append(f"requests {trial[0]}-{trial[-1]}: {bad}")
            worst["trial"] = max(worst["trial"],
                                 ref.worst(got_total, want_total))
    for idx in (i for i in sorted(sampler.sum) if i in kept):
        root, scores, depth = kept[idx]
        lv = ref.levels(root)
        bad = ref.check_sum(scores, root, lv)
        if bad is None and depth < int(lv.max()) + 1:
            bad = (f"root {root}: its batch reported {depth} levels, the "
                   f"root alone has {int(lv.max()) + 1}")
        if bad:
            problems.append(f"request {idx}: {bad}")
        rule = ref.sum_rule(root, lv)
        if rule:
            worst["sum"] = max(worst["sum"], abs(
                float(scores.astype(np.float64).sum()) - rule) / rule)
    serving.log(
        "bc: largest relative error against float64: one score "
        f"{worst['exact']:.3e}, a trial's sum {worst['trial']:.3e} (limit "
        f"{bcref.RTOL:g}); an answer's sum against the sum rule "
        f"{worst['sum']:.3e} (limit {bcref.RTOL_SUM:g})")
    return problems


def log_layers(job, ctx: dict) -> None:
    """Each of the kind's readers on this run's ``ctx``, logged; a reader
    that finds nothing (no device plane, no counter) says so."""
    ctx = dict(ctx, device=job.device)
    for name in LAYERS:
        value = job.spec.load_module("layers", name).read(ctx)
        serving.log(f"layer {name}: " + (
            "nothing to read" if value is None else repr(float(value))))


def log_waves(whole, completions, levels) -> None:
    """What ``qps`` is made of: a wave is one batch, and a batch whose
    deepest root has one BFS level more runs two sweeps more, so a
    window's rate is set by how many of its seven or eight waves drew
    such a root.  Logged wave by wave: the levels of the batch that
    ended it, and the seconds and the rate since the wave before."""
    order = np.argsort(completions)
    at, rows, prev = 0, [], None
    for end, count in whole:
        lv = sorted({levels[i] for i in order[at:at + count]})
        at += count
        took = "" if prev is None else (
            f" {end - prev:.3f} s {count / (end - prev):.3f}/s")
        rows.append("/".join(map(str, lv)) + " levels" + took)
        prev = end
    serving.log("bc: waves that ended in the window: " + "; ".join(rows))


def run(job) -> dict:
    mix = job.mix
    ses = serving.Session(job)
    dep = ses.dep
    kind, in_flight = mix["kind"], int(mix["in_flight"])
    trial = int(mix["trial"])
    sampler = BCSampler(job.seed, trial, int(mix["check"]["exact"]),
                        int(mix["check"]["sum"]), dep.deg)
    roots = graph.draw_roots(dep.deg, job.seed, 4096)
    root_of = lambda i: int(roots[i % len(roots)])
    done = queue.SimpleQueue()
    completions, levels = [], []

    def submit_trial(first: int) -> None:
        sampler.submitted(first)
        futures = ses.srv.submit_many(
            kind, [root_of(i) for i in range(first, first + trial)])
        for i, fut in enumerate(futures, first):
            fut.add_done_callback(
                lambda f, i=i: done.put((i, time.perf_counter(), f))
            )

    t_first = ses.open_window()
    t_end = t_first + job.seconds
    sent = 0
    while sent + trial <= in_flight:
        submit_trial(sent)
        sent += trial
    open_ = sent
    deadline = t_end + float(mix["drain_s"])
    while open_:
        try:
            i, t_done, fut = done.get(
                timeout=max(deadline - time.perf_counter(), 0.01)
            )
        except queue.Empty:
            break
        open_ -= 1
        if fut.exception() is not None:
            ses.failures.append(repr(fut.exception()))
        else:
            # (the worker is a daemon thread: nothing to close first, and
            # a close would wait out the batch now on the device)
            require_scores(fut.result())
            sampler.take(i, root_of(i), fut.result())
            completions.append(t_done)
            levels.append(int(fut.result()["batch_niter"]))
        del fut
        while open_ + trial <= in_flight and time.perf_counter() < t_end:
            submit_trial(sent)
            sent += trial
            open_ += trial
    # the session's own checks are BFS's: hand them a sampler that wants
    # and keeps nothing, and add this kind's
    compiles, problems, ctx = ses.close_window(
        serving.Sampler(job.seed, 0, 0)
    )
    t0 = time.perf_counter()
    ref = bcref.BCReference(dep.n, dep.rows, dep.cols, bfs=dep.reference())
    problems += check_sample(ref, sampler)
    serving.log(f"bc: checked {len(sampler.kept)} sampled answers "
                f"in {time.perf_counter() - t0:.1f} s")
    whole = [w for w in loadgen.waves(completions) if w[0] <= t_end]
    ctx["waves"] = whole
    ctx["bc_cost"] = {
        "n": dep.n, "slots": cost.ell_slots(dep.engine.E),
        "width": max(int(w) for w in job.cfg["lane_widths"]),
    }
    serving.log(f"{sum(c for _, c in whole)} completions in {len(whole)} "
                "whole waves inside the window")
    log_waves(whole, completions, levels)
    if job.trace:
        log_layers(job, ctx)
    return {
        "attempted": sent,
        "failed": sent - len(completions),
        "problems": problems,
        "compiles_in_window": compiles,
        "t_first_send": t_first,
        "values": {"qps": loadgen.wave_rate(completions, until=t_end)},
        "ctx": ctx,
    }
