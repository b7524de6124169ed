"""Device time by ``jax.named_scope`` and by BFS level, from the traced
run's ``.xplane.pb``.

A v5e trace names each device operation by the compiler's instruction
text (``%fusion.249 = s32[...] fusion(...)``) and carries no scope; the
scopes are in the ``op_name`` metadata of the compiled program, which
the program publishes at warm-up as ``{instruction: op_name}`` per
module (``combblas_tpu.obs.opnames``).  This file joins the two: self
time of every operation of the dominant program's whole executions,
charged to the scope of its instruction; the iterations of the program's
``bfs.level`` loop as levels; and the host plane's ``serve.*``
annotations (written by the program on the profiler's own clock) laid
over the first device's idle gaps.

Where the program publishes no table or the trace holds no scoped
operation (the parent of the PR that added the scopes; a CPU rehearsal,
which has no device plane), every reading is None, never 0.

The scope names are the yardstick: ``SCOPES`` below is the documented
list (``combblas_tpu/models/bfs.py:BFS_SCOPES``, docs/observability.md).
Checked on ``tests/chipbench/data/tiny_scoped.xplane.pb``.
"""

from __future__ import annotations

import glob
import os
import re
import statistics

from chipbench import devtrace
from chipbench.deploy import log

#: path components of an ``op_name`` that are scopes (everything else is
#: a transform, a nested jit or the primitive's own name)
SCOPES = re.compile(
    r"^(bfs\.(init|level|update|active|parents)|ell\.(bucket\d+|reduce)"
    r"|gather|fold|scatter_rows|vec\.realign)$"
)
LOOP = "bfs.level"
HOST_PREFIX = "serve."


def label(op_name: str | None) -> str | None:
    """``jit(f)/bfs.level/while/body/jit(g)/ell.bucket3/gather/gather``
    -> ``bfs.level/ell.bucket3/gather``: the scopes on the path, the
    last component (the primitive's name) left out.  None: no scope."""
    if not op_name:
        return None
    found = [c for c in op_name.split("/")[:-1] if SCOPES.match(c)]
    if not found and SCOPES.match(op_name):
        found = [op_name]
    return "/".join(found) or None


def instruction(event_name: str) -> str:
    """``%fusion.249 = s32[...] fusion(...)`` -> ``fusion.249``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_serve_bfs_w16(1172754028435489344)`` -> the module."""
    return event_name.split("(", 1)[0]


def newest_trace(ctx) -> str | None:
    """The newest ``.xplane.pb`` under the cache's ``profile/``: beside
    the configuration in use, else in this checkout."""
    roots = [os.path.join(os.path.dirname(os.path.abspath(__file__)), ".cache")]
    cfg_file = (ctx.get("cfg") or {}).get("_file")
    if cfg_file:
        roots.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(cfg_file)), ".cache"
        ))
    for root in roots:
        found = glob.glob(os.path.join(
            root, "profile", "*", "plugins", "profile", "*", "*.xplane.pb"
        ))
        if found:
            return max(found, key=os.path.getmtime)
    return None


def published_tables() -> dict:
    """``{module: {instruction: op_name}}`` the program published, {}
    where it has no such thing."""
    try:
        from combblas_tpu.obs import opnames
    except ImportError:
        return {}
    return opnames.tables()


def _direct_children(events, parent):
    """Events lying directly inside ``parent`` (not inside another event
    that does), in time order."""
    inside = sorted(
        (e for e in events
         if e is not parent and e[1] >= parent[1] and e[2] <= parent[2]),
        key=lambda e: (e[1], -(e[2] - e[1])),
    )
    out, end = [], parent[1]
    for e in inside:
        if e[1] >= end:
            out.append(e)
            end = e[2]
    return out


def self_times_by_label(ops, table) -> dict:
    """``ops``: ``(instruction, start, end)`` of one execution, nested.
    ``{label | None: seconds}`` with every instant charged to the
    innermost operation covering it, under that operation's scope.  An
    operation whose instruction carries no scope (the compiler's own
    slices, copies and inner loops have no metadata at all) takes the
    scope of the operation it runs inside: work inside the ``bfs.level``
    loop is at least ``bfs.level``'s."""
    evs = sorted(ops, key=lambda e: (e[1], -(e[2] - e[1])))
    own = [e[2] - e[1] for e in evs]
    labels, stack = [], []
    for i, (instr, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        lab = label(table.get(instr))
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
            if lab is None:
                lab = labels[stack[-1]]
        labels.append(lab)
        stack.append(i)
    out = {}
    for lab, t in zip(labels, own):
        out[lab] = out.get(lab, 0.0) + max(t, 0.0)
    return out


def split_levels(events, loop) -> list[float]:
    """Seconds of each iteration of the ``loop`` event: the loop's first
    direct child opens every iteration (it recurs once per iteration),
    so iteration k runs from its k-th occurrence to the next, the last
    one to the loop's end.  A trailing stub holding under half the
    operations of a typical iteration (the condition, evaluated once
    more than the body) belongs to the iteration before it."""
    kids = _direct_children(events, loop)
    if not kids:
        return []
    opens = [i for i, e in enumerate(kids) if e[0] == kids[0][0]]
    sizes = [b - a for a, b in zip(opens, opens[1:] + [len(kids)])]
    if len(opens) > 1 and sizes[-1] < 0.5 * statistics.median(sizes):
        opens.pop()
    bounds = [kids[i][1] for i in opens] + [loop[2]]
    bounds[0] = loop[1]
    return [b - a for a, b in zip(bounds, bounds[1:])]


def reduce_scopes(source, tables: dict) -> dict | None:
    """Reduce one trace against the published tables.  ``source``: a
    path, serialized bytes or a ``ProfileData``.  None when no device
    plane executed a whole program.  Otherwise::

        {"module": name, "executions": whole executions per device,
         "device_s": mean seconds of one execution,
         "by_scope": {label: mean seconds per execution} | None,
         "unscoped_s": mean seconds per execution under no scope,
         "levels": [[seconds per level] per whole execution] | None,
         "host": [(annotation, start, end)] of the host plane}

    ``by_scope`` and ``levels`` are None when no operation of the
    program carries a scope (no table, or a program without scopes).
    """
    from jax.profiler import ProfileData

    if isinstance(source, (bytes, bytearray)):
        pd = ProfileData.from_serialized_xspace(source)
    elif isinstance(source, str):
        pd = ProfileData.from_file(source)
    else:
        pd = source
    planes, host = {}, []
    for plane in pd.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if devtrace.OPS_LINE in lines and devtrace.MODULES_LINE in lines:
                planes[plane.name] = tuple(
                    devtrace._line_events(lines[nm])
                    for nm in (devtrace.OPS_LINE, devtrace.MODULES_LINE)
                )
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [
                    e for e in devtrace._line_events(ln)
                    if e[0].startswith(HOST_PREFIX)
                ]
    # whole executions of every program, per device (devtrace's rule: an
    # execution touching the first or last instant of the plane is cut)
    whole, total = {}, {}
    for name, (ops, mods) in planes.items():
        first = min((e[1] for e in ops), default=0.0)
        last = max((e[2] for e in ops), default=0.0)
        for m in mods:
            if m[1] > first + devtrace.EDGE_S and m[2] < last - devtrace.EDGE_S:
                mod = module_name(m[0])
                whole.setdefault(mod, []).append((name, m))
                total[mod] = total.get(mod, 0.0) + (m[2] - m[1])
    if not total:
        return None
    mod = max(total, key=total.get)
    table = tables.get(mod, {})
    runs = whole[mod]
    by_scope, unscoped, levels = {}, 0.0, []
    for plane_name, m in runs:
        ops = [
            (instruction(e[0]), e[1], e[2]) for e in planes[plane_name][0]
            if e[1] >= m[1] and e[2] <= m[2]
        ]
        for lab, secs in self_times_by_label(ops, table).items():
            if lab is None:
                unscoped += secs
            else:
                by_scope[lab] = by_scope.get(lab, 0.0) + secs
        loops = [e for e in ops if label(table.get(e[0])) == LOOP
                 and e[0].startswith("while")]
        if loops:
            levels.append(split_levels(
                ops, max(loops, key=lambda e: e[2] - e[1])
            ))
    n = len(runs)
    return {
        "module": mod,
        "executions": n // max(len({p for p, _ in runs}), 1),
        "device_s": total[mod] / n,
        "by_scope": (
            {k: v / n for k, v in by_scope.items()} if by_scope else None
        ),
        "unscoped_s": unscoped / n,
        "levels": levels or None,
        "host": sorted(host, key=lambda e: e[1]),
    }


def idle_by_annotation(reduced: dict, host) -> list:
    """Idle seconds of the first device inside the reduced window, by
    the innermost ``serve.*`` annotation of the host plane covering
    them (``no-annotation`` otherwise): both are on the profiler's
    clock, so there is no offset to get wrong."""
    if not reduced or not reduced["devices"] or reduced["window"] is None:
        return []
    busy = next(iter(reduced["devices"].values()))["busy"]
    w0, w1 = reduced["window"]
    gaps, cur = [], w0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        gaps.append((cur, w1))
    acc = {}
    # innermost = the shortest covering annotation: charge shortest first
    spans = sorted(host, key=lambda e: e[2] - e[1])
    for g0, g1 in gaps:
        left = [(g0, g1)]
        for name, s, e in spans:
            nxt = []
            for a, b in left:
                lo, hi = max(a, s), min(b, e)
                if hi > lo:
                    acc[name] = acc.get(name, 0.0) + (hi - lo)
                    nxt += [(a, lo)] if lo > a else []
                    nxt += [(hi, b)] if b > hi else []
                else:
                    nxt.append((a, b))
            left = nxt
        rest = sum(b - a for a, b in left)
        if rest > 1e-9:
            acc["no-annotation"] = acc.get("no-annotation", 0.0) + rest
    return sorted(([k, v] for k, v in acc.items()), key=lambda kv: -kv[1])


def by_phase(by_scope: dict) -> dict:
    """``by_scope`` folded over the degree classes: ``bfs.level/gather``
    is the sum of ``bfs.level/ell.bucket<i>/gather`` over ``i``."""
    out = {}
    for lab, secs in by_scope.items():
        key = "/".join(
            c for c in lab.split("/") if not c.startswith("ell.bucket")
        )
        out[key] = out.get(key, 0.0) + secs
    return out


def level_table(levels) -> list[float]:
    """Mean seconds of level k over the executions that ran one."""
    depth = max((len(lv) for lv in levels), default=0)
    return [
        statistics.fmean(lv[k] for lv in levels if len(lv) > k)
        for k in range(depth)
    ]


def scoped(ctx) -> dict | None:
    """``reduce_scopes`` of this run's trace, once per run (kept in
    ``ctx``); logs the table by scope and by level the first time."""
    if "_scoped" in ctx:
        return ctx["_scoped"]
    ctx["_scoped"] = None
    path = newest_trace(ctx) if ctx.get("trace") else None
    if path is None:
        return None
    red = reduce_scopes(path, published_tables())
    ctx["_scoped"] = red
    if red is None:
        return None
    log(f"scopes: {red['module']}, {red['executions']} whole executions "
        f"a device, {1e3 * red['device_s']:.1f} ms each")
    if red["by_scope"] is None:
        log("scopes: no operation of it carries a scope")
    else:
        for title, table in (("phase", by_phase(red["by_scope"])),
                             ("scope", red["by_scope"])):
            for lab, secs in sorted(table.items(), key=lambda kv: -kv[1]):
                log(f"{title} {lab}: {1e3 * secs:.3f} ms "
                    f"({100 * secs / red['device_s']:.2f}%)")
        log(f"scope <none>: {1e3 * red['unscoped_s']:.3f} ms "
            f"({100 * red['unscoped_s'] / red['device_s']:.2f}%)")
    if red["levels"]:
        log("levels run by execution: "
            + " ".join(str(len(lv)) for lv in red["levels"]))
        log("ms by level, first to last (mean over executions): "
            + " ".join(f"{1e3 * s:.2f}" for s in level_table(red["levels"])))
    for name, secs in idle_by_annotation(ctx.get("trace"), red["host"])[:8]:
        log(f"idle under {name}: {secs:.4f} s")
    return red


def share(ctx, leaves=("gather", "fold")) -> float | None:
    """Self time under scopes ending in one of ``leaves`` over the
    program's device time (%)."""
    red = scoped(ctx)
    if not red or red["by_scope"] is None:
        return None
    hit = sum(v for k, v in red["by_scope"].items()
              if k.rsplit("/", 1)[-1] in leaves)
    return 100.0 * hit / red["device_s"]


def level_ms(ctx) -> float | None:
    """Median over the levels of whole executions of one iteration of
    ``bfs.level`` (ms)."""
    red = scoped(ctx)
    flat = [s for lv in (red or {}).get("levels") or [] for s in lv]
    return 1e3 * statistics.median(flat) if flat else None


def scope_ms(ctx, prefix: str) -> float | None:
    """Self time per execution under scopes starting with ``prefix``
    (ms)."""
    red = scoped(ctx)
    if not red or red["by_scope"] is None:
        return None
    hit = [v for k, v in red["by_scope"].items()
           if k == prefix or k.startswith(prefix + "/")]
    return 1e3 * sum(hit) if hit else None
