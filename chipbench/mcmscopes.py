"""Device time of a matching job's program by ``jax.named_scope`` and by
phase, from the traced run's ``.xplane.pb``.

``scopes.py`` does the whole reduction (the dominant program's whole
executions, self time by scope, the iterations of the program's loop)
but fixes its pattern and its loop to the BFS names.  This program's
scopes (``combblas_tpu/models/matching.py:MCM_SCOPES``): ``mcm.init``
(the Karp-Sipser rounds, ``.push`` / ``.sweep`` inside), ``mcm.phase``
(the ``while`` whose iteration is one augmenting phase) with ``mcm.bfs``
(``.push`` / ``.sweep`` inside), ``mcm.chase`` and ``mcm.augment``
inside it; ``ell.bucket<i>`` and the leaf names under a sweep are
shared.  So the published tables are handed to ``scopes.reduce_scopes``
with ``mcm.phase`` spelled as the loop it knows and the others as degree
classes no matrix has, and what comes back is spelled as the program
spells it (``ccscopes.py``'s shim: PERF.md section 7).

Where the program publishes no table or the trace holds no scoped
operation (a program without these scopes; a CPU rehearsal, which has no
device plane), every reading is None, never 0.
"""

from __future__ import annotations

from chipbench import scopes
from chipbench.deploy import log

LOOP = "mcm.phase"
_REST = ("mcm.init", "mcm.init.push", "mcm.init.sweep", "mcm.bfs",
         "mcm.bfs.push", "mcm.bfs.sweep", "mcm.chase", "mcm.augment")
_AS_SCOPES_PY = dict(
    {LOOP: scopes.LOOP},
    **{name: f"ell.bucket{9100 + k}" for k, name in enumerate(_REST)},
)
_AS_PROGRAM = {v: k for k, v in _AS_SCOPES_PY.items()}


def _respell(path: str, names: dict) -> str:
    return "/".join(names.get(c, c) for c in path.split("/"))


def reduce_scopes(source, tables: dict) -> dict | None:
    """``scopes.reduce_scopes`` for a program under ``MCM_SCOPES``:
    ``by_scope`` keyed ``mcm.phase/mcm.bfs/mcm.bfs.push``, ``levels``
    the seconds of each phase of each whole execution."""
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
    runs in a cell of this kind); logs the table by scope and by phase
    the first time."""
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
    table = dict(scopes.by_phase(red["by_scope"]),
                 **{"<none>": red["unscoped_s"]})
    for lab, secs in sorted(table.items(), key=lambda kv: -kv[1]):
        log(f"scope {lab}: {1e3 * secs:.3f} ms "
            f"({100 * secs / red['device_s']:.2f}%)")
    if red["levels"]:
        log("phases run by execution: "
            + " ".join(str(len(lv)) for lv in red["levels"]))
        log("ms by phase, first to last (mean over executions): " + " ".join(
            f"{1e3 * s:.2f}" for s in scopes.level_table(red["levels"])))
    return red


def under_ms(ctx, scope: str) -> float | None:
    """Self time a whole execution under scopes whose path holds
    ``scope`` (ms)."""
    red = scoped(ctx)
    if not red or red["by_scope"] is None:
        return None
    hit = [v for k, v in red["by_scope"].items() if scope in k.split("/")]
    return 1e3 * sum(hit) if hit else None


def phase_ms(ctx) -> float | None:
    """Median over the phases of whole executions of one iteration of
    ``mcm.phase`` (ms)."""
    scoped(ctx)
    return scopes.level_ms(ctx)
