"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  Per device
plane (``/device:TPU:<i>``): the union of the intervals in which an
operation ran (busy), each operation's self time under the compiler's
name (a ``while`` is charged only what its body does not cover), the
time in collective operations, and the executions of each program (the
``XLA Modules`` line).  Idle gaps are attributed to what the host was
doing in them, from host spans the driver hands over on the same clock:
the profiler's time base is tied to the host's by one annotated anchor
event written right after the trace starts.

The reduction is checked on a small recorded trace
(``tests/chipbench/data/tiny.xplane.pb``).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import threading
import time

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANCHOR = "chipbench_anchor"
EDGE_S = 5e-8  # closer than this to the first or last op: cut by the trace
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast|\bsend\b|\brecv\b"
)


def merge(intervals):
    """Union of ``(start, end)`` intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events):
    """``events``: ``(name, start, end)`` on one line, possibly nested.
    Returns ``{name: seconds}`` with every instant charged to the
    innermost event covering it."""
    evs = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    own = [e[2] - e[1] for e in evs]
    stack = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
        stack.append(i)
    out = {}
    for (name, _, _), t in zip(evs, own):
        out[name] = out.get(name, 0.0) + max(t, 0.0)
    return out


def _line_events(line):
    return [
        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
        for e in line.events
    ]


def reduce_xplane(source, window=None, slice_s=None) -> dict:
    """Reduce one trace.  ``source`` is a path, serialized bytes or a
    ``ProfileData``; ``window`` is ``(start, end)`` in trace seconds to clip to, or
    ``slice_s`` seconds from the anchor event (default: first device
    event start to last device event end).

    Returns ``{"devices": {plane: {...}}, "window": (s, e),
    "window_s", "busy_s" (mean over devices), "anchor_s"}``; per device
    ``busy_s``, ``busy`` (disjoint intervals), ``ops`` (self seconds by
    name), ``collective_s``, ``modules`` ``{name: [count, seconds]}`` over the
    executions recorded whole.
    """
    from jax.profiler import ProfileData

    if isinstance(source, (bytes, bytearray)):
        pd = ProfileData.from_serialized_xspace(source)
    elif isinstance(source, str):
        pd = ProfileData.from_file(source)
    else:
        pd = source
    raw = {}
    anchor = None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get(OPS_LINE)
            if ops is None:
                continue
            mods = lines.get(MODULES_LINE)
            raw[plane.name] = (
                _line_events(ops),
                _line_events(mods) if mods is not None else [],
            )
        elif anchor is None and plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == ANCHOR:
                        anchor = e.start_ns * 1e-9
                        break
                if anchor is not None:
                    break
    if window is None and slice_s is not None and anchor is not None:
        window = (anchor, anchor + slice_s)
    if window is None and raw:
        flat = [ev for ops, _ in raw.values() for ev in ops]
        if flat:
            window = (min(e[1] for e in flat), max(e[2] for e in flat))
    devices = {}
    for name, (ops, mods) in sorted(raw.items()):
        # a program already running when the trace starts, or still
        # running when it stops, is recorded only in part: such an
        # execution touches the first or the last instant the device
        # plane knows of, and is left out of the per-execution times
        first = min((e[1] for e in ops), default=0.0)
        last = max((e[2] for e in ops), default=0.0)
        mods = [m for m in mods
                if m[1] > first + EDGE_S and m[2] < last - EDGE_S]
        if window is not None:
            w0, w1 = window
            ops = [
                (nm, max(s, w0), min(e, w1)) for nm, s, e in ops
                if e > w0 and s < w1
            ]
            mods = [m for m in mods if m[1] >= w0 and m[2] <= w1]
        busy = merge([(s, e) for _, s, e in ops])
        modules = {}
        for nm, s, e in mods:
            c = modules.setdefault(nm, [0, 0.0])
            c[0] += 1
            c[1] += e - s
        devices[name] = {
            "busy": busy,
            "busy_s": sum(e - s for s, e in busy),
            "ops": self_times(ops),
            "collective_s": sum(
                e - s for nm, s, e in ops if COLLECTIVE.search(nm.lower())
            ),
            "modules": modules,
        }
    n = len(devices)
    return {
        "devices": devices,
        "window": window,
        "window_s": (window[1] - window[0]) if window else 0.0,
        "busy_s": (
            sum(d["busy_s"] for d in devices.values()) / n if n else 0.0
        ),
        "anchor_s": anchor,
    }


def inventory(pd) -> list[str]:
    """Planes and lines of a trace with their event counts (logged by the
    traced run, so a trace the reduction cannot read says why)."""
    out = []
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        if plane.name.startswith("/host:"):
            lines = [x for x in lines if x[1]][:4]
        out.append(f"{plane.name}: " + ", ".join(
            f"{nm} ({c})" for nm, c in lines
        ))
    return out


def top_ops(reduced: dict, k: int = 10):
    """The device operations that took most time: self seconds by the
    compiler's name, averaged over the devices used."""
    total = {}
    n = max(len(reduced["devices"]), 1)
    for d in reduced["devices"].values():
        for nm, t in d["ops"].items():
            total[nm] = total.get(nm, 0.0) + t / n
    return [
        [nm, t] for nm, t in
        sorted(total.items(), key=lambda kv: -kv[1])[:k]
    ]


def dominant_module(reduced: dict):
    """``(name, executions, seconds per execution)`` of the program that
    took most device time, over all devices and over the executions the
    trace holds whole (a program run across four chips executes once on
    each: executions are per device)."""
    total = {}
    for d in reduced["devices"].values():
        for nm, (c, t) in d["modules"].items():
            a = total.setdefault(nm, [0, 0.0])
            a[0] += c
            a[1] += t
    if not total:
        return None
    nm, (c, t) = max(total.items(), key=lambda kv: kv[1][1])
    return nm, c // max(len(reduced["devices"]), 1), t / c


def idle_gaps(reduced: dict, host_spans, offset: float, k: int = 10):
    """Idle seconds of the first device, by what the host was doing.
    ``host_spans``: ``(label, t0, t1)`` on the host's clock; ``offset`` =
    host time minus trace time.  A gap inside a span is named
    ``<label>:before-device`` / ``:between-ops`` / ``:after-device`` by
    where the device's work inside that span lies; a gap under no span
    is ``no-host-span``."""
    if not reduced["devices"] or reduced["window"] is None:
        return []
    busy = next(iter(reduced["devices"].values()))["busy"]
    starts = [s for s, _ in busy]
    ends = [e for _, e in busy]
    w0, w1 = reduced["window"]
    gaps, cur = [], w0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        gaps.append((cur, w1))
    spans = []
    for lab, t0, t1 in sorted(host_spans, key=lambda x: x[1]):
        s0, s1 = t0 - offset, t1 - offset
        # the device's work inside this span: first start, last end
        lo = bisect.bisect_right(ends, s0)
        hi = bisect.bisect_left(starts, s1)
        first = max(starts[lo], s0) if lo < hi else None
        last = min(ends[hi - 1], s1) if lo < hi else None
        spans.append((s0, s1, lab, first, last))
    span_starts = [sp[0] for sp in spans]
    acc = {}

    def charge(label, secs):
        if secs > 1e-9:  # under a nanosecond: float noise of the offset
            acc[label] = acc.get(label, 0.0) + secs

    for g0, g1 in gaps:
        covered = []
        # spans are few and mostly disjoint: scan those starting before g1
        for s0, s1, lab, first, last in spans[:bisect.bisect_left(
                span_starts, g1)]:
            a, b = max(g0, s0), min(g1, s1)
            if b <= a:
                continue
            if first is None or b <= first:
                where = "before-device"
            elif a >= last:
                where = "after-device"
            else:
                where = "between-ops"
            charge(f"{lab}:{where}", b - a)
            covered.append((a, b))
        charge("no-host-span",
               (g1 - g0) - sum(e - s for s, e in merge(covered)))
    return [
        [lab, t] for lab, t in sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    ]


class SliceTracer:
    """Profiles a slice of the window from a side thread: ``start_s``
    after the first send, for ``seconds`` (data of the mix: two or three
    batches, not the window).  Host tracing is kept to annotations (no
    Python tracer: it slows the host the server runs on)."""

    def __init__(self, outdir: str, start_s: float, seconds: float, log):
        shutil.rmtree(outdir, ignore_errors=True)
        self.outdir, self.log = outdir, log
        self.start_s, self.seconds = start_s, seconds
        self.anchor_host = None  # time.time() at the anchor event
        self.slice_s = None  # the slice's length on the host's clock
        self._thread = None

    def _body(self, t_first_send: float) -> None:
        import jax

        time.sleep(max(
            t_first_send + self.start_s - time.perf_counter(), 0.0
        ))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.outdir, profiler_options=opts)
        self.anchor_host = time.time()
        with jax.profiler.TraceAnnotation(ANCHOR):
            time.sleep(0.001)
        t0 = time.perf_counter()
        time.sleep(self.seconds)
        self.slice_s = time.perf_counter() - t0
        jax.profiler.stop_trace()

    def begin(self, t_first_send: float) -> None:
        self._thread = threading.Thread(
            target=self._body, args=(t_first_send,), name="chipbench-trace"
        )
        self._thread.start()

    def finish(self):
        """``(reduced trace, host-minus-trace clock offset)``, or
        ``(None, None)`` when nothing was traced."""
        if self._thread is None:
            return None, None
        self._thread.join()
        found = sorted(glob.glob(os.path.join(
            self.outdir, "plugins", "profile", "*", "*.xplane.pb"
        )))
        if not found:
            return None, None
        from jax.profiler import ProfileData

        t0 = time.perf_counter()
        pd = ProfileData.from_file(found[-1])
        for row in inventory(pd):
            self.log(f"trace plane {row}")
        red = reduce_xplane(pd, slice_s=self.slice_s)
        self.log(
            f"trace {found[-1]} reduced in {time.perf_counter() - t0:.1f} s:"
            f" {len(red['devices'])} device planes, busy "
            f"{red['busy_s']:.3f} s of {red['window_s']:.3f} s"
        )
        if red["anchor_s"] is None:
            return red, None
        return red, self.anchor_host - red["anchor_s"]
