"""Device time of the FastSV program by ``jax.named_scope`` and by
round, from the traced run's ``.xplane.pb``.

``scopes.py`` does the whole reduction (the dominant program's whole
executions, self time by scope, the iterations of the program's loop)
but fixes its pattern and its loop to the BFS names.  This program's
scopes (``combblas_tpu/models/cc.py:CC_SCOPES``) are seven: ``cc.init``,
``cc.iter`` (the ``while`` whose iteration is one FastSV round) with
``cc.gather``, ``cc.spmv``, ``cc.hook`` and ``cc.min`` inside it, and
``cc.jump`` (the pointer-jumping loop after it); ``ell.bucket<i>`` and
the leaf names under ``cc.spmv`` are shared.  So the published tables
are handed to ``scopes.reduce_scopes`` with ``cc.iter`` spelled as the
loop it knows and the six others as degree classes no matrix has (its
pattern takes any ``ell.bucket<number>``; BFS's five names would not go
round), and what comes back is spelled as the program spells it (the
fourth such shim: PERF.md section 7).

Where the program publishes no table or the trace holds no scoped
operation (a program without these scopes; a CPU rehearsal, which has no
device plane), every reading is None, never 0.
"""

from __future__ import annotations

from chipbench import scopes
from chipbench.deploy import log

LOOP = "cc.iter"
_REST = ("cc.init", "cc.gather", "cc.spmv", "cc.hook", "cc.min", "cc.jump")
_AS_SCOPES_PY = dict(
    {LOOP: scopes.LOOP},
    **{name: f"ell.bucket{9000 + k}" for k, name in enumerate(_REST)},
)
_AS_PROGRAM = {v: k for k, v in _AS_SCOPES_PY.items()}


def _respell(path: str, names: dict) -> str:
    return "/".join(names.get(c, c) for c in path.split("/"))


def reduce_scopes(source, tables: dict) -> dict | None:
    """``scopes.reduce_scopes`` for a program under ``CC_SCOPES``:
    ``by_scope`` keyed ``cc.iter/cc.spmv/ell.bucket3/gather``,
    ``levels`` the seconds of each round of each whole execution."""
    red = scopes.reduce_scopes(source, {
        mod: {i: _respell(nm, _AS_SCOPES_PY) for i, nm in table.items()}
        for mod, table in tables.items()
    })
    if red and red["by_scope"] is not None:
        red["by_scope"] = {
            _respell(lab, _AS_PROGRAM): s
            for lab, s in red["by_scope"].items()
        }
    return red


def scoped(ctx) -> dict | None:
    """``reduce_scopes`` of this run's trace, once per run, kept in
    ``ctx`` where ``scopes.py``'s readers look for theirs (no BFS reader
    runs in a cell of this kind), so ``scopes.level_ms`` reads it as it
    is; logs the table by phase and by round the first time."""
    if "_scoped" in ctx:
        return ctx["_scoped"]
    ctx["_scoped"] = None
    path = scopes.newest_trace(ctx) if ctx.get("trace") else None
    if path is None:
        return None
    red = ctx["_scoped"] = reduce_scopes(path, scopes.published_tables())
    if red is None:
        return None
    log(f"scopes: {red['module']}, {red['executions']} whole executions "
        f"a device, {1e3 * red['device_s']:.1f} ms each")
    if red["by_scope"] is None:
        log("scopes: no operation of it carries a scope")
        return red
    for title, table in (
            ("phase", dict(scopes.by_phase(red["by_scope"]),
                           **{"<none>": red["unscoped_s"]})),
            ("scope", red["by_scope"])):
        for lab, secs in sorted(table.items(), key=lambda kv: -kv[1]):
            log(f"{title} {lab}: {1e3 * secs:.3f} ms "
                f"({100 * secs / red['device_s']:.2f}%)")
    if red["levels"]:
        log("rounds run by execution: "
            + " ".join(str(len(lv)) for lv in red["levels"]))
        log("ms by round, first to last (mean over executions): " + " ".join(
            f"{1e3 * s:.2f}" for s in scopes.level_table(red["levels"])))
    return red


def round_ms(ctx) -> float | None:
    """Median over the rounds of whole executions of one iteration of
    ``cc.iter`` (ms)."""
    scoped(ctx)
    return scopes.level_ms(ctx)


def share(ctx, under: tuple) -> float | None:
    """Self time under scopes whose path holds one of ``under`` over the
    program's device time (%)."""
    red = scoped(ctx)
    if not red or red["by_scope"] is None:
        return None
    hit = sum(v for k, v in red["by_scope"].items()
              if set(k.split("/")) & set(under))
    return 100.0 * hit / red["device_s"]


def rounds_per_job(ctx=None) -> float | None:
    """Counter ``models.cc.rounds`` over ``models.cc.jobs``: rounds of a
    job, the round that changed nothing included, mean over the jobs the
    wrapper ran (the warm-up job too: every job runs the same rounds)."""
    from chipbench.parts import counter

    rounds, jobs = counter("models.cc.rounds"), counter("models.cc.jobs")
    return rounds / jobs if rounds is not None and jobs else None
