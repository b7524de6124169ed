"""Load arithmetic: the open-loop schedule, latency from the scheduled
send, the tail taken block by block, and throughput between whole waves.

The schedule and latency rules are copied from ``serve/net/loadgen.py``
(seeded Poisson arrivals drawn up front, the pacer never waits for a
completion, latency counts from the SCHEDULED send) so that they live
where a later PR cannot change them.  numpy only.
"""

from __future__ import annotations

import numpy as np


def poisson_offsets(seed: int, rate: float, seconds: float):
    """Arrival offsets (s) of a seeded Poisson process: a fixed count
    ``int(rate * seconds)`` of exponential gaps, all drawn before the
    clock starts."""
    rng = np.random.default_rng([seed, 0xA771])
    count = max(int(rate * seconds), 1)
    return np.cumsum(rng.exponential(1.0 / rate, count))


def latencies(t_first: float, offsets, t_done):
    """Latency of each request from its SCHEDULED send (``t_first +
    offset``) to its completion: a late generator or a full queue is
    charged to the request, never hidden (no coordinated omission)."""
    return np.asarray(t_done, np.float64) - (
        t_first + np.asarray(offsets, np.float64)
    )


def blocked_percentile(values, q: float, blocks: int) -> float:
    """The median over ``blocks`` consecutive blocks of the schedule of
    each block's ``q``-th percentile.  One stall (of the generator's
    host, or one queueing episode) fills the pooled tail of a whole
    window by itself; it fills one block here, and the median over the
    blocks does not move.  ``values`` are in schedule order."""
    parts = [b for b in np.array_split(np.asarray(values, np.float64),
                                       max(int(blocks), 1)) if len(b)]
    return float(np.median([np.percentile(b, q) for b in parts]))


def waves(times, gap_ratio: float = 10.0, min_gap_s: float = 0.02):
    """Group completion timestamps into waves.  A served batch settles
    its futures in one pass, microseconds to milliseconds apart, then
    nothing completes for one execution: a new wave starts at a gap
    above ``gap_ratio`` times the median gap (and above ``min_gap_s``).
    Returns a list of ``(end_time, count)``."""
    t = np.sort(np.asarray(times, np.float64))
    if len(t) == 0:
        return []
    gaps = np.diff(t)
    if len(gaps) == 0:
        return [(float(t[0]), 1)]
    cut = max(gap_ratio * float(np.median(gaps)), min_gap_s)
    ends = np.flatnonzero(gaps > cut)
    bounds = np.concatenate([ends, [len(t) - 1]])
    out, prev = [], -1
    for b in bounds:
        out.append((float(t[b]), int(b - prev)))
        prev = int(b)
    return out


def wave_rate(times, until: float | None = None, **kw) -> float | None:
    """Completions per second between the ENDS of whole waves: the work
    of waves 2..K over the time from wave 1's end to wave K's end, so a
    window that cuts a wave in two never quantises the rate.  ``times``
    are all completions, the drain's too; a wave counts when it ENDS by
    ``until`` (the window's end).  With fewer than three waves (a system
    that settles continuously) it is the plain rate between the first
    and the last completion by ``until``."""
    w = waves(times, **kw)
    if until is not None:
        w = [x for x in w if x[0] <= until]
    if len(w) >= 3:
        span = w[-1][0] - w[0][0]
        return sum(c for _, c in w[1:]) / span if span > 0 else None
    t = np.sort(np.asarray(times, np.float64))
    if until is not None:
        t = t[t <= until]
    if len(t) < 2 or t[-1] <= t[0]:
        return None
    return (len(t) - 1) / float(t[-1] - t[0])
