"""Device time of one sparse-product job ON A MESH by ``jax.named_scope``
and by device, from the traced run's ``.xplane.pb``.

``sqscopes.py``'s reduction (a job is the program's own ``spgemm.job``
annotation on the profiler's clock; inside it every device operation is
charged its self time under the scope of its instruction, read from the
table its program published) made on EVERY device plane, since a job on
a mesh is the same programs run once a chip and its wall is the slowest
chip's: the by-scope readers take the BUSIEST device (most busy seconds
inside a job), the skew and the collectives' share compare the planes.
Beside ``SQ_SCOPES`` it knows the mesh path's two
(``combblas_tpu/parallel/spgemm.py:SQ_MESH_SCOPES``): ``sq.exchange``
(the stage exchange of operand tiles) and ``sq.pack`` (every tile cut
to what it stores).

Where the program writes no such annotation or publishes no table (a
program without the job entry; a CPU rehearsal, which has no device
plane), every reading is None, never 0.
"""

from __future__ import annotations

import bisect
import re

from chipbench import cost, devtrace, scopes, sqcost, sqscopes
from chipbench.deploy import log
from chipbench.parts import counter

SCOPES = re.compile(
    r"^sq\.(symbolic|densify|dot|extract|digest|exchange|pack)$")


def label(op_name: str | None) -> str | None:
    """The outermost of the job's scopes on an ``op_name``'s path."""
    for c in (op_name or "").split("/"):
        if SCOPES.match(c):
            return c
    return None


def self_by_label(ops, table: dict) -> tuple[dict, float]:
    """``ops``: ``(instruction, start, end, name)`` of one execution,
    nested.  ``({label | None: seconds}, collective seconds)``: every
    instant charged to the innermost operation covering it, under that
    operation's scope or, where its instruction carries none, the scope
    of the operation it runs in; and the self time of the operations
    the compiler names as collectives (``devtrace.COLLECTIVE``)."""
    evs = sorted(ops, key=lambda e: (e[1], -(e[2] - e[1])))
    own = [e[2] - e[1] for e in evs]
    labels, stack = [], []
    for i, (instr, s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        lab = label(table.get(instr))
        if stack:
            own[stack[-1]] -= min(e, evs[stack[-1]][2]) - s
            if lab is None:
                lab = labels[stack[-1]]
        labels.append(lab)
        stack.append(i)
    out, coll = {}, 0.0
    for lab, t, ev in zip(labels, own, evs):
        out[lab] = out.get(lab, 0.0) + max(t, 0.0)
        if devtrace.COLLECTIVE.search(ev[3].lower()):
            coll += max(t, 0.0)
    return out, coll


def _plane(ops, mods, jobs, tables: dict) -> dict:
    """One device plane over the whole jobs ``jobs`` (``(start, end)``
    of the host's annotations, in order): means a job.  Operations and
    programs are sorted by their starts once and cut by bisection: a
    slice of four planes holds millions of operations."""
    ops = sorted(ops, key=lambda o: o[1])
    mods = sorted(mods, key=lambda m: m[1])
    op_at, mod_at = [o[1] for o in ops], [m[1] for m in mods]
    by_scope, unscoped, busy, coll, modules = {}, 0.0, 0.0, 0.0, {}
    done = None
    for s, e in jobs:
        # as ``sqscopes.reduce_jobs``: a job takes what ran since the
        # job before it closed
        lo, done = (s - sqscopes.ALIGN_S if done is None else done), e
        i0, i1 = bisect.bisect_left(op_at, lo), bisect.bisect_left(op_at, e)
        busy += sum(b - a for a, b in devtrace.merge(
            [(o[1], o[2]) for o in ops[i0:i1] if o[2] <= e]))
        seen = {}
        for m in mods[bisect.bisect_left(mod_at, lo):
                      bisect.bisect_left(mod_at, e)]:
            if m[2] > e:
                continue
            mod = scopes.module_name(m[0])
            nth = seen[mod] = seen.get(mod, -1) + 1
            table = tables.get(f"{mod}#{nth}", tables.get(mod, {}))
            acc = modules.setdefault(mod, [0, 0.0])
            acc[0] += 1
            acc[1] += m[2] - m[1]
            mine = [
                (scopes.instruction(o[0]), o[1], o[2], o[0])
                for o in ops[bisect.bisect_left(op_at, m[1], i0, i1):
                             bisect.bisect_right(op_at, m[2], i0, i1)]
                if o[2] <= m[2]]
            labelled, c = self_by_label(mine, table)
            coll += c
            for lab, secs in labelled.items():
                if lab is None:
                    unscoped += secs
                else:
                    by_scope[lab] = by_scope.get(lab, 0.0) + secs
    n = len(jobs)
    return {
        "device_s": busy / n,
        "collective_s": coll / n,
        "by_scope": (
            {k: v / n for k, v in by_scope.items()} if by_scope else None),
        "unscoped_s": unscoped / n,
        "modules": {k: [c / n, t / n] for k, (c, t) in modules.items()},
    }


def reduce_jobs(source, tables: dict) -> dict | None:
    """Reduce one trace against the published tables.  None when the
    trace holds no whole job on a device plane.  Otherwise::

        {"jobs": whole jobs, "wall_s": mean seconds of the annotation,
         "devices": {plane: {"device_s": mean busy seconds inside a job,
                             "collective_s": ... in collective operations,
                             "by_scope": {label: mean seconds} | None,
                             "unscoped_s", "modules"}},
         "busiest": the plane with the largest ``device_s``}

    A job counts when EVERY device plane holds it whole."""
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
                ops, mods = (
                    devtrace._line_events(lines[nm])
                    for nm in (devtrace.OPS_LINE, devtrace.MODULES_LINE))
                if ops:
                    planes[plane.name] = (ops, mods)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [e for e in devtrace._line_events(ln)
                         if e[0] == sqscopes.HOST_JOB]
    if not planes:
        return None
    first = max(min(e[1] for e in ops) for ops, _ in planes.values())
    last = min(max(e[2] for e in ops) for ops, _ in planes.values())
    jobs = [(s, e) for _, s, e in sorted(host, key=lambda e: e[1])
            if s > first + devtrace.EDGE_S and e < last - devtrace.EDGE_S]
    if not jobs:
        return None
    devices = {name: _plane(ops, mods, jobs, tables)
               for name, (ops, mods) in sorted(planes.items())}
    return {
        "jobs": len(jobs),
        "wall_s": sum(e - s for s, e in jobs) / len(jobs),
        "devices": devices,
        "busiest": max(devices, key=lambda k: devices[k]["device_s"]),
    }


def scoped(ctx) -> dict | None:
    """``reduce_jobs`` of this run's trace, once per run, kept in
    ``ctx``; logs the busiest device's table by scope, every device's
    busy and collective time, the programs of a job and the share of the
    matrix unit's peak one chip's dense products issue the first time."""
    if "_sqm_scoped" in ctx:
        return ctx["_sqm_scoped"]
    ctx["_sqm_scoped"] = None
    path = scopes.newest_trace(ctx) if ctx.get("trace") else None
    if path is None:
        return None
    red = ctx["_sqm_scoped"] = reduce_jobs(path, scopes.published_tables())
    if red is None:
        log("scopes: the trace holds no device plane or no whole "
            f"{sqscopes.HOST_JOB!r} annotation")
        return None
    log(f"scopes: {red['jobs']} whole jobs, {1e3 * red['wall_s']:.1f} ms "
        f"each on the host, on {len(red['devices'])} devices")
    for name, d in red["devices"].items():
        log(f"device {name}: busy {1e3 * d['device_s']:.1f} ms a job, "
            f"{1e3 * d['collective_s']:.3f} ms of it in collectives"
            + (" (the busiest)" if name == red["busiest"] else ""))
    top = red["devices"][red["busiest"]]
    for mod, (count, secs) in sorted(
            top["modules"].items(), key=lambda kv: -kv[1][1]):
        log(f"program {mod}: {count:g} executions a job, "
            f"{1e3 * secs:.3f} ms")
    if top["by_scope"] is None:
        log("scopes: no operation of a job carries a scope")
        return red
    table = dict(top["by_scope"], **{"<none>": top["unscoped_s"]})
    for lab, secs in sorted(table.items(), key=lambda kv: -kv[1]):
        log(f"scope {lab}: {1e3 * secs:.3f} ms "
            f"({100 * secs / top['device_s']:.2f}%)")
    flops, jobs = counter("spgemm.job.dense_flops"), counter(
        "spgemm.job.jobs")
    if flops and jobs:
        peak = cost.peaks(ctx["device"]["kind"])["bf16_tflops"]
        log(f"one chip's dense stage products issue {flops / jobs:.4g} "
            "flop a job: "
            f"{sqcost.dense_flop_share(flops / jobs, top['device_s'], peak):.2f}% "
            f"of {peak} TFLOP/s over the busiest device's time")
    for name in ("stages", "exchange_bytes", "tile_nnz_max", "tile_nnz_min",
                 "pack_capacity"):
        total = counter(f"spgemm.job.{name}")
        if total and jobs:
            log(f"counter spgemm.job.{name}: {total / jobs:g} a job")
    return red


def busiest(ctx) -> dict | None:
    red = scoped(ctx)
    return red["devices"][red["busiest"]] if red else None


def device_ms(ctx) -> float | None:
    top = busiest(ctx)
    return 1e3 * top["device_s"] if top else None


def scope_ms(ctx, under: tuple) -> float | None:
    """Self time a job on the busiest device under the scopes in
    ``under`` (ms)."""
    top = busiest(ctx)
    if not top or top["by_scope"] is None:
        return None
    hit = [v for k, v in top["by_scope"].items() if k in under]
    return 1e3 * sum(hit) if hit else None
