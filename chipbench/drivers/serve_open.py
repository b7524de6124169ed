"""Open loop over ``Server.submit``: a seeded Poisson schedule drawn up
front at the mix's fixed ``rate``; the pacer sleeps to each arrival and
sends whatever is still in flight; latency counts from the SCHEDULED
send to the future's result.  After the last send the rest drain.

``p50_ms`` is the median over all requests.  ``p95_ms`` is the median over
``tail_blocks`` consecutive blocks of the schedule of each block's 95th
percentile (``loadgen.blocked_percentile``); the 95th percentile over all
requests at once is kept beside it for the ``p95_pooled_ms`` reader.

Mix parameters: ``kind``, ``rate`` (requests/s, a number fixed in the
file, never searched for), ``tail_blocks``, ``drain_s``, ``check``,
``trace``.
"""

from __future__ import annotations

import queue
import time

import numpy as np

from chipbench import graph, loadgen, serving


def run(job) -> dict:
    mix = job.mix
    ses = serving.Session(job)
    kind = mix["kind"]
    offsets = loadgen.poisson_offsets(
        job.seed, float(mix["rate"]), job.seconds
    )
    count = len(offsets)
    roots = graph.draw_roots(ses.dep.deg, job.seed, count)
    sampler = serving.Sampler(job.seed, count, int(mix["check"]["tree"]))
    done = queue.SimpleQueue()
    t_done_of = np.full(count, np.nan)
    late = np.zeros(count)
    settled = 0

    def settle(block_until: float | None) -> bool:
        """Take one completion off the queue (waiting until the given
        time, or not at all)."""
        nonlocal settled
        try:
            if block_until is None:
                i, t_done, fut = done.get_nowait()
            else:
                i, t_done, fut = done.get(
                    timeout=max(block_until - time.perf_counter(), 0.001)
                )
        except queue.Empty:
            return False
        settled += 1
        if fut.exception() is not None:
            ses.failures.append(repr(fut.exception()))
        else:
            sampler.take(i, int(roots[i]), fut.result())
            t_done_of[i] = t_done
        return True

    t_first = ses.open_window()
    for i in range(count):
        target = t_first + offsets[i]
        while time.perf_counter() < target:
            # between sends: settle what has completed, else sleep on
            if not settle(None):
                time.sleep(min(max(target - time.perf_counter(), 0.0),
                               0.002))
        late[i] = time.perf_counter() - target
        try:
            fut = ses.srv.submit(kind, int(roots[i]))
        except RuntimeError as e:  # backpressure, an open breaker, a
            # closed server: rejected at the door is a failed request
            ses.failures.append(repr(e))
            settled += 1
            continue
        fut.add_done_callback(
            lambda f, i=i: done.put((i, time.perf_counter(), f))
        )
    deadline = time.perf_counter() + float(mix["drain_s"])
    while settled < count and time.perf_counter() < deadline:
        settle(deadline)
    compiles, problems, ctx = ses.close_window(sampler)

    latency = loadgen.latencies(t_first, offsets, t_done_of)
    ok = latency[np.isfinite(latency)]
    blocks = int(mix.get("tail_blocks", 1))
    ctx.update(late_s=late, latency_s=ok)
    serving.log(
        f"{len(ok)} of {count} completed; generator late median "
        f"{1e3 * float(np.median(late)):.3f} ms, max "
        f"{1e3 * float(late.max()):.3f} ms"
    )
    values = {}
    if len(ok):
        values = {
            "p50_ms": 1e3 * float(np.percentile(ok, 50)),
            "p95_ms": 1e3 * loadgen.blocked_percentile(ok, 95, blocks),
        }
        ctx["p95_pooled_ms"] = 1e3 * float(np.percentile(ok, 95))
        serving.log(
            f"latency from the scheduled send: p50 {values['p50_ms']:.0f} "
            f"ms, p95 {values['p95_ms']:.0f} ms (median of {blocks} "
            f"blocks), {ctx['p95_pooled_ms']:.0f} ms over all {len(ok)} at "
            "once; p50 / p95 by block: " + ", ".join(
                f"{1e3 * np.percentile(t, 50):.0f} / "
                f"{1e3 * np.percentile(t, 95):.0f}"
                for t in np.array_split(ok, blocks) if len(t))
        )
    return {
        "attempted": count,
        "failed": count - len(ok),
        "problems": problems,
        "compiles_in_window": compiles,
        "t_first_send": t_first,
        "values": values,
        "ctx": ctx,
    }
