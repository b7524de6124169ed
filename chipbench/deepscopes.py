"""Where a deep wave's device time goes: the traced run's log, by the
walk's own scopes and by level index.

``chipbench/scopes.py`` charges every operation to the scopes of its
``SCOPES`` pattern, which has no ``bfs.push``: a walked level's
operations read ``bfs.level`` there.  This file lays the same trace
under the walk's scopes as well (``bfs.push`` and, inside it,
``push.columns``, ``push.lay``, ``push.walk``, ``push.scatter``): self
time of every operation of the wave's program, by the scopes on its
``op_name``.  It reports nothing: it logs, once a traced run, what
``PERF.md`` section 5 quotes.  A program without the scopes (the parent)
or a trace without a device plane logs nothing.
"""

from __future__ import annotations

import re
import statistics

from chipbench import devtrace, scopes
from chipbench.deploy import log

WALK = re.compile(r"^(bfs\.push|push\.(columns|lay|walk|scatter))$")


def label(op_name: str | None) -> str:
    """``.../bfs.level/while/body/cond/branch_1_fun/bfs.push/while/body/
    push.walk/gather`` -> ``bfs.level/bfs.push/push.walk``."""
    found = [c for c in (op_name or "").split("/")[:-1]
             if scopes.SCOPES.match(c) or WALK.match(c)]
    return "/".join(found) or "<none>"


def by_walk_scope(path: str, tables: dict) -> dict | None:
    """``{label: seconds a whole execution}`` of the program that took
    most device time on the first device plane, None where there is no
    such plane or no table."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if not {devtrace.OPS_LINE, devtrace.MODULES_LINE} <= set(lines):
            continue
        ops = devtrace._line_events(lines[devtrace.OPS_LINE])
        mods = devtrace._line_events(lines[devtrace.MODULES_LINE])
        first, last = min(e[1] for e in ops), max(e[2] for e in ops)
        whole = {}
        for m in mods:
            if (m[1] > first + devtrace.EDGE_S
                    and m[2] < last - devtrace.EDGE_S):
                whole.setdefault(scopes.module_name(m[0]), []).append(m)
        if not whole:
            return None
        mod = max(whole, key=lambda k: sum(m[2] - m[1] for m in whole[k]))
        table = tables.get(mod)
        if not table:
            return None
        runs = whole[mod]
        inside = [e for e in ops
                  if any(m[1] <= e[1] and e[2] <= m[2] for m in runs)]
        out = {}
        for name, secs in devtrace.self_times(inside).items():
            lab = label(table.get(scopes.instruction(name)))
            out[lab] = out.get(lab, 0.0) + secs / len(runs)
        return out
    return None


def log_breakdown(ctx) -> None:
    """Once a traced run: ms a wave by scope (the walk's included), and
    ms a level by level index, in twentieths of the wave's depth."""
    if ctx.get("_deep_logged") or not ctx.get("trace"):
        return
    ctx["_deep_logged"] = True
    path = scopes.newest_trace(ctx)
    table = by_walk_scope(path, scopes.published_tables()) if path else None
    if not table:
        return
    total = sum(table.values())
    for lab, secs in sorted(table.items(), key=lambda kv: -kv[1])[:24]:
        log(f"deep scope {lab}: {1e3 * secs:.2f} ms a wave "
            f"({100 * secs / total:.2f}%)")
    red = scopes.scoped(ctx)
    for lv in ((red or {}).get("levels") or [])[:2]:
        cuts = [round(len(lv) * k / 20) for k in range(21)]
        log(f"deep levels: {len(lv)} of one wave, mean ms a level by "
            "twentieth of its depth: " + " ".join(
                f"{1e3 * statistics.fmean(lv[a:b]):.2f}"
                for a, b in zip(cuts, cuts[1:]) if b > a)
            + f"; first 8: " + " ".join(f"{1e3 * s:.2f}" for s in lv[:8])
            + f"; last 8: " + " ".join(f"{1e3 * s:.2f}" for s in lv[-8:]))
