"""Device time of one clustering job by ``jax.named_scope`` and by
iteration, from the traced run's ``.xplane.pb``.

A clustering job is tens of programs: one a dense iteration, two a
sparse one, the walk between the two states, the interpretation, the
host between them all.  As in ``sqscopes.py`` the unit is the JOB, found
by the annotation the program itself writes on the profiler's clock (the
host span ``mcl.job``), and a job counts when the trace holds it whole.
Inside it every device operation is charged its self time under the
scope of its instruction (``combblas_tpu/models/mcl.py:MCL_SCOPES``),
read from the table its program published (``combblas_tpu.obs.opnames``;
a function launched under several static signatures publishes one table
a launch, ``<module>#<nth>``).  The components' program is another
module's (``models/cc.py``) and carries no MCL scope: it is charged to
``mcl.interpret`` whole.  The job's ``mcl.iter`` annotations, in order,
give the wall and the device's busy time of every iteration.

Where the program writes no such annotation or publishes no table (a
program without the job entry; a CPU rehearsal, which has no device
plane), every reading is None, never 0.
"""

from __future__ import annotations

import re

from chipbench import cost, devtrace, mclcost, scopes
from chipbench.deploy import log
from chipbench.parts import counter

HOST_JOB = "mcl.job"
HOST_ITER = "mcl.iter"
#: as ``sqscopes.ALIGN_S``: how far the two planes of one trace may
#: disagree
ALIGN_S = 1e-3
SCOPES = re.compile(
    r"^mcl\.(symbolic|expand|select|chaos|inflate|interpret)$")
#: programs of other modules a job launches, by the scope they serve
MODULE_SCOPE = {"jit_cc_fastsv": "mcl.interpret"}


def label(op_name: str | None) -> str | None:
    """``jit(f)/mcl.expand/sq.dot/dot_general`` -> ``mcl.expand``: the
    outermost of the job's scopes on the path.  None: no scope."""
    for c in (op_name or "").split("/"):
        if SCOPES.match(c):
            return c
    return None


def self_by_label(ops, table: dict, default: str | None = None) -> dict:
    """``ops``: ``(instruction, start, end)`` of one execution, nested.
    ``{label | None: seconds}``: every instant charged to the innermost
    operation covering it, under that operation's scope or, where its
    instruction carries none, the scope of the operation it runs in
    (``default`` at the top)."""
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
        elif lab is None:
            lab = default
        labels.append(lab)
        stack.append(i)
    out = {}
    for lab, t in zip(labels, own):
        out[lab] = out.get(lab, 0.0) + max(t, 0.0)
    return out


def _busy(ops, lo, hi) -> float:
    """Busy seconds of the operations that END in ``(lo, hi]``."""
    return sum(b - a for a, b in devtrace.merge(
        [(o[1], o[2]) for o in ops if lo < o[2] <= hi]))


def reduce_jobs(source, tables: dict) -> dict | None:
    """Reduce one trace against the published tables.  None when the
    trace holds no whole job on a device plane.  Otherwise::

        {"jobs": whole jobs, "wall_s": mean seconds of the annotation,
         "device_s": mean busy seconds of the device inside one,
         "by_scope": {label: mean seconds a job} | None,
         "unscoped_s": mean seconds a job under no scope,
         "modules": {module: [executions a job, seconds a job]},
         "iters": [[wall_s, device_s] an iteration, mean over the jobs
                   that ran it]}

    ``by_scope`` is None when no operation of a job carries a scope."""
    from jax.profiler import ProfileData

    if isinstance(source, (bytes, bytearray)):
        pd = ProfileData.from_serialized_xspace(source)
    elif isinstance(source, str):
        pd = ProfileData.from_file(source)
    else:
        pd = source
    device, host, iters = None, [], []
    for plane in pd.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if device is None and devtrace.OPS_LINE in lines \
                    and devtrace.MODULES_LINE in lines:
                device = tuple(
                    devtrace._line_events(lines[nm])
                    for nm in (devtrace.OPS_LINE, devtrace.MODULES_LINE))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in devtrace._line_events(ln):
                    if e[0] == HOST_JOB:
                        host.append(e)
                    elif e[0] == HOST_ITER:
                        iters.append(e)
    if device is None or not device[0]:
        return None
    ops, mods = device
    first = min(e[1] for e in ops)
    last = max(e[2] for e in ops)
    jobs = [(s, e) for _, s, e in sorted(host, key=lambda e: e[1])
            if s > first + devtrace.EDGE_S and e < last - devtrace.EDGE_S]
    if not jobs:
        return None
    by_scope, unscoped, busy, wall, modules = {}, 0.0, 0.0, 0.0, {}
    by_iter = []
    done = None
    for s, e in jobs:
        # as sqscopes: a job takes what ran since the job before it
        # closed, so every operation between the first and the last
        # whole job is counted once.  By its END: a job's first program
        # is launched as its annotation opens, and where the device's
        # plane runs a little ahead of the host's it seems to start
        # before it (my chip runs, PR 44: ``_mcl_start`` fell out of
        # every job that way)
        lo, done = (s - ALIGN_S if done is None else done), e
        inside = [o for o in ops if lo < o[2] <= e]
        busy += _busy(inside, lo, e)
        wall += e - s
        mine = sorted((i for i in iters if i[1] >= s and i[2] <= e),
                      key=lambda i: i[1])
        for k, (_, a, b) in enumerate(mine):
            # an iteration takes what ran since the one before it
            # closed (the job's first programs with the first)
            nxt = mine[k + 1][1] if k + 1 < len(mine) else b + ALIGN_S
            if k == len(by_iter):
                by_iter.append([0, 0.0, 0.0])
            by_iter[k][0] += 1
            by_iter[k][1] += b - a
            by_iter[k][2] += _busy(inside, a - ALIGN_S if k else lo, nxt)
        seen = {}
        for m in sorted((m for m in mods if lo < m[2] <= e),
                        key=lambda m: m[1]):
            mod = scopes.module_name(m[0])
            nth = seen[mod] = seen.get(mod, -1) + 1
            table = tables.get(f"{mod}#{nth}", tables.get(mod, {}))
            acc = modules.setdefault(mod, [0, 0.0])
            acc[0] += 1
            acc[1] += m[2] - m[1]
            in_m = [(scopes.instruction(o[0]), o[1], o[2])
                    for o in inside if o[1] >= m[1] and o[2] <= m[2]]
            for lab, secs in self_by_label(
                    in_m, table, MODULE_SCOPE.get(mod)).items():
                if lab is None:
                    unscoped += secs
                else:
                    by_scope[lab] = by_scope.get(lab, 0.0) + secs
    n = len(jobs)
    return {
        "jobs": n,
        "wall_s": wall / n,
        "device_s": busy / n,
        "by_scope": (
            {k: v / n for k, v in by_scope.items()} if by_scope else None),
        "unscoped_s": unscoped / n,
        "modules": {k: [c / n, t / n] for k, (c, t) in modules.items()},
        "iters": [[w / c, d / c] for c, w, d in by_iter],
    }


def scoped(ctx) -> dict | None:
    """``reduce_jobs`` of this run's trace, once per run, kept in
    ``ctx``; the first time it logs the table by scope, the programs of
    a job, its iterations and the share of the matrix unit's peak its
    dense products issue."""
    if "_mcl_scoped" in ctx:
        return ctx["_mcl_scoped"]
    ctx["_mcl_scoped"] = None
    path = scopes.newest_trace(ctx) if ctx.get("trace") else None
    if path is None:
        return None
    red = ctx["_mcl_scoped"] = reduce_jobs(path, scopes.published_tables())
    if red is None:
        log(f"scopes: the trace holds no device plane or no whole "
            f"{HOST_JOB!r} annotation")
        return None
    log(f"scopes: {red['jobs']} whole jobs, {1e3 * red['wall_s']:.1f} ms "
        f"each on the host, the device busy {1e3 * red['device_s']:.1f} ms "
        "of it")
    for mod, (count, secs) in sorted(
            red["modules"].items(), key=lambda kv: -kv[1][1]):
        log(f"program {mod}: {count:g} executions a job, "
            f"{1e3 * secs:.3f} ms")
    log("ms by iteration (wall/device): " + " ".join(
        f"{1e3 * w:.1f}/{1e3 * d:.1f}" for w, d in red["iters"]))
    if red["by_scope"] is None:
        log("scopes: no operation of a job carries a scope")
        return red
    table = dict(red["by_scope"], **{"<none>": red["unscoped_s"]})
    for lab, secs in sorted(table.items(), key=lambda kv: -kv[1]):
        log(f"scope {lab}: {1e3 * secs:.3f} ms "
            f"({100 * secs / red['device_s']:.2f}%)")
    flops, jobs = counter("mcl.job.dense_flops"), counter("mcl.job.jobs")
    if flops and jobs:
        peak = cost.peaks(ctx["device"]["kind"])["bf16_tflops"]
        log(f"the dense iterations' products issue {flops / jobs:.4g} flop "
            f"a job: "
            f"{mclcost.dense_flop_share(flops / jobs, red['device_s'], peak):.2f}% "
            f"of {peak} TFLOP/s over the job's device time")
    return red


def device_ms(ctx) -> float | None:
    red = scoped(ctx)
    return 1e3 * red["device_s"] if red else None


def scope_ms(ctx, under: tuple) -> float | None:
    """Self time a job under the scopes in ``under`` (ms)."""
    red = scoped(ctx)
    if not red or red["by_scope"] is None:
        return None
    hit = [v for k, v in red["by_scope"].items() if k in under]
    return 1e3 * sum(hit) if hit else None
