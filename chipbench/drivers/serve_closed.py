"""Closed loop over ``Server.submit``: ``in_flight`` requests always
outstanding, every completion replaced at once by a new root, until the
window ends; then the rest drain.

Mix parameters: ``kind``, ``in_flight``, ``drain_s`` (how long the drain
may take before what is left counts as failed), ``check`` (``exact``,
``tree``: how many sampled answers get which check), ``trace``
(``start_s``, ``seconds``: the profiled slice).
"""

from __future__ import annotations

import queue
import time

from chipbench import graph, loadgen, serving


def run(job) -> dict:
    mix = job.mix
    ses = serving.Session(job)
    kind, in_flight = mix["kind"], int(mix["in_flight"])
    sampler = serving.Sampler(
        job.seed, 2 * in_flight, int(mix["check"]["tree"])
    )
    roots = graph.draw_roots(ses.dep.deg, job.seed, 4096)
    root_of = lambda i: int(roots[i % len(roots)])
    done = queue.SimpleQueue()
    completions = []

    def submit(i: int) -> None:
        ses.srv.submit(kind, root_of(i)).add_done_callback(
            lambda f, i=i: done.put((i, time.perf_counter(), f))
        )

    t_first = ses.open_window()
    t_end = t_first + job.seconds
    for sent in range(in_flight):
        submit(sent)
    sent = open_ = in_flight
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
            sampler.take(i, root_of(i), fut.result())
            completions.append(t_done)
        del fut
        if time.perf_counter() < t_end:
            submit(sent)
            sent += 1
            open_ += 1
    compiles, problems, ctx = ses.close_window(sampler)
    whole = [w for w in loadgen.waves(completions) if w[0] <= t_end]
    ctx["waves"] = whole
    serving.log(f"{sum(c for _, c in whole)} completions in {len(whole)} "
                "whole waves inside the window")
    return {
        "attempted": sent,
        "failed": sent - len(completions),
        "problems": problems,
        "compiles_in_window": compiles,
        "t_first_send": t_first,
        "values": {"qps": loadgen.wave_rate(completions, until=t_end)},
        "ctx": ctx,
    }
