"""A boot's timeline, from the program's own spans: what the six set-up
readers (``layers/graph_ready_s.py`` ... ``layers/boot_unspanned_s.py``)
share.

The program records a span at every boundary a boot crosses
(``serve.restore`` or ``serve.load``, ``serve.engine.init``,
``serve.server.init``, ``serve.warmup.companion``, one ``serve.warmup``
a plan, ``obs.opnames.publish``), each with ``t0`` on ``perf_counter``,
and attaches JAX's own seconds for tracing, lowering, fetching and
compiling to the span they ran under as events ``{name, s, t}``.
``setup_s`` is taken on the same clock (``run.T_PROCESS_START`` to the
first send), so the boot is what lies before ``T_PROCESS_START +
setup_s``.

Events nest (an outer function's ``trace`` holds its inner jits', a
``compile`` holds the ``fetch`` of a cache hit), so seconds are measures
of UNIONS of the intervals ``[t - s, t]``, never sums.  Events under a
span named ``obs.opnames.publish`` are the probe's (what a traced boot
pays for being traced) and count in ``boot_probe_s`` alone.

A program that records no ``t0`` in its spans, or has no reader of its
span log (the parent of the PR that added both), gives None everywhere.
The first reader of a run also logs the timeline: one line a top-level
span, one a plan.
"""

from __future__ import annotations

import sys

from chipbench.deploy import log

PROBE = "obs.opnames.publish"
MADE = ("serve.restore", "serve.load")  # the span that made the version
KINDS = ("trace", "lower", "fetch", "compile")


def union_s(intervals, lo=float("-inf"), hi=float("inf")) -> float:
    """Seconds covered by ``(start, end)`` intervals, inside [lo, hi]."""
    total, reach = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            total += b - a
            reach = b
    return total


def process_start() -> float | None:
    """``run.T_PROCESS_START``: under ``python3 -m chipbench.run`` the
    module is ``__main__``; a caller of ``run.main`` has it by name."""
    for name in ("__main__", "chipbench.run"):
        t = getattr(sys.modules.get(name), "T_PROCESS_START", None)
        if t is not None:
            return float(t)
    return None


def span_log() -> tuple[list, list] | None:
    """``(closed spans, span-less events)`` of the program, or None
    where it has no reader of them (``obs.spans`` is then the submodule
    of that name)."""
    from combblas_tpu import obs

    if not callable(getattr(obs, "spans", None)):
        return None
    return obs.spans(), obs.events()


def _intervals(events, names) -> list[tuple]:
    return [(e["t"] - e["s"], e["t"]) for e in events if e["name"] in names]


def timeline(spans, top_events, t_start: float, t_first: float) -> dict | None:
    """The boot between ``t_start`` and ``t_first`` (``perf_counter``),
    from a span log.  None where the spans carry no ``t0``."""
    spans = [s for s in spans if "t0" in s and s["t0"] < t_first]
    if not spans:
        return None
    top = sorted((s for s in spans if "/" not in s["path"]),
                 key=lambda s: s["t0"])
    setup_s = t_first - t_start
    out = {"setup_s": setup_s, "top": top, "plans": []}
    out["spanned_s"] = union_s(
        [(s["t0"], s["t0"] + s["wall_s"]) for s in top], t_start, t_first)
    out["boot_unspanned_s"] = setup_s - out["spanned_s"]

    # the version that is served, and the engine around it
    made = [s for s in top if s["name"] in MADE and not s.get("failed")]
    out["graph_ready_s"] = out["upload_s"] = None
    if made:
        made = made[-1]
        end = made["t0"] + made["wall_s"]
        init = [s for s in top if s["name"] == "serve.engine.init"
                and s["t0"] >= made["t0"]]
        children = {}
        for s in spans:
            head, _, name = s["path"].rpartition("/")
            if head == made["path"] and made["t0"] <= s["t0"] <= end:
                children[name] = children.get(name, 0.0) + s["wall_s"]
        out["made"] = made
        out["children"] = children
        out["graph_ready_s"] = made["wall_s"] + (
            init[0]["wall_s"] if init else 0.0)
        out["upload_s"] = (children.get("upload", 0.0)
                           + children.get("companion", 0.0))

    # JAX's seconds: the probe's events apart from the boot's own
    def jax_events(events):
        return [e for e in events if e["name"] in KINDS and "s" in e
                and e.get("t", t_first) < t_first]

    mine, probes = jax_events(top_events), []
    for s in spans:
        (probes if s["name"] == PROBE else mine).extend(
            jax_events(s.get("events", ())))
    out["boot_trace_s"] = union_s(_intervals(mine, ("trace", "lower")))
    out["boot_fetch_s"] = union_s(_intervals(mine, ("fetch", "compile")))
    out["events"] = {k: union_s(_intervals(mine, (k,))) for k in KINDS}
    out["probe_events"] = {
        k: union_s(_intervals(probes, (k,))) for k in KINDS}

    # what the boot paid for being traced
    probe = sum(s["wall_s"] for s in top if s["name"] == PROBE)
    for s in top:
        if s["name"] != "serve.warmup":
            continue
        parts = {p["stage"]: p["s"] for p in s.get("parts", ())}
        probe += parts.get("probe", 0.0)
        # the execute part's events: trace, lower, fetch, compile, and
        # what is left of the part is the program's first run
        t_build = s["t0"] + parts.get("build", 0.0)
        t_exec = t_build + parts.get("execute", 0.0)
        inside = [e for e in jax_events(s.get("events", ()))
                  if t_build < e["t"] <= t_exec]
        by = {k: union_s(_intervals(inside, (k,))) for k in KINDS}
        by["compile"] = max(by["compile"] - by["fetch"], 0.0)
        out["plans"].append(dict(
            by, attrs=s.get("attrs", {}), wall_s=s["wall_s"],
            build=parts.get("build"), execute=parts.get("execute"),
            probe=parts.get("probe", 0.0),
            first_run=(parts["execute"] - union_s(_intervals(inside, KINDS))
                       if "execute" in parts else None),
        ))
    out["boot_probe_s"] = probe
    return out


def log_timeline(tl: dict, t_start: float) -> None:
    for s in tl["top"]:
        attrs = s.get("attrs", {})
        what = " ".join(f"{k}={attrs[k]}" for k in ("kind", "width")
                        if k in attrs)
        log(f"boot span {s['name']:24s} {what:18s} at "
            f"{s['t0'] - t_start:8.3f} s  wall {s['wall_s']:8.3f} s")
    if "made" in tl:
        attrs = tl["made"].get("attrs", {})
        log(f"boot {tl['made']['name']}: " + ", ".join(
            f"{k} {v:.3f} s" for k, v in sorted(tl["children"].items()))
            + "".join(f", {k} {attrs[k]}" for k in (
                "file_bytes", "host_bytes", "device_bytes") if k in attrs))
    for p in tl["plans"]:
        a = p["attrs"]
        log(f"boot plan {a.get('kind')} w{a.get('width')}: " + ", ".join(
            f"{k} {p[k]:.3f}" for k in (
                "build", "trace", "lower", "fetch", "compile", "first_run",
                "probe") if p[k] is not None) + f" (wall {p['wall_s']:.3f} s)")
    log("boot events outside the probe: " + ", ".join(
        f"{k} {v:.3f}" for k, v in tl["events"].items())
        + "; under the probe: " + ", ".join(
        f"{k} {v:.3f}" for k, v in tl["probe_events"].items()))
    log(f"boot: setup_s {tl['setup_s']:.3f} = spanned "
        f"{tl['spanned_s']:.3f} + unspanned {tl['boot_unspanned_s']:.3f}")


def boot(ctx) -> dict | None:
    """This run's timeline (``timeline``), computed and logged once a
    run (kept in ``ctx``)."""
    if "_boot" not in ctx:
        ctx["_boot"] = None
        t_start = process_start()
        setup_s = (ctx.get("values") or {}).get("setup_s")
        logs = span_log()
        if None not in (t_start, setup_s, logs):
            ctx["_boot"] = timeline(*logs, t_start, t_start + setup_s)
            if ctx["_boot"] is not None:
                log_timeline(ctx["_boot"], t_start)
    return ctx["_boot"]


def read(ctx, metric: str) -> float | None:
    tl = boot(ctx)
    return None if tl is None else tl[metric]
