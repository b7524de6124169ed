"""Device time of the served kernel-3 program by ``jax.named_scope`` and
by Bellman-Ford round, from the traced run's ``.xplane.pb``.

``scopes.py`` does the whole reduction (the dominant program's whole
executions, self time by scope, the iterations of the program's loop)
but fixes its pattern and its loop to the BFS names.  This program's
scopes (``combblas_tpu/models/sssp.py:SSSP_SCOPES``) take the same three
places: ``sssp.init`` before the loop, ``sssp.round`` the ``while``,
``sssp.parents`` the one sweep after it; ``ell.bucket<i>`` and the leaf
names are shared.  So the published tables are handed to
``scopes.reduce_scopes`` with those three names spelled as it knows them,
and what comes back is spelled as the program spells them.

Where the program publishes no table or the trace holds no scoped
operation (a program without these scopes; a CPU rehearsal, which has no
device plane), every reading is None, never 0.
"""

from __future__ import annotations

from chipbench import scopes
from chipbench.deploy import log

LOOP = "sssp.round"
_AS_SCOPES_PY = {"sssp.init": "bfs.init", LOOP: scopes.LOOP,
                 "sssp.parents": "bfs.parents"}
_AS_PROGRAM = {v: k for k, v in _AS_SCOPES_PY.items()}


def _respell(path: str, names: dict) -> str:
    return "/".join(names.get(c, c) for c in path.split("/"))


def reduce_scopes(source, tables: dict) -> dict | None:
    """``scopes.reduce_scopes`` for a program under ``SSSP_SCOPES``:
    ``by_scope`` keyed ``sssp.round/ell.bucket3/gather``, ``levels`` the
    seconds of each round of each whole execution."""
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
    runs in a cell of this kind), so ``scopes.level_ms`` / ``scope_ms`` /
    ``share`` read it as it is; logs the table by phase and by round the
    first time."""
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
    phases = dict(scopes.by_phase(red["by_scope"]),
                  **{"<none>": red["unscoped_s"]})
    for lab, secs in sorted(phases.items(), key=lambda kv: -kv[1]):
        log(f"phase {lab}: {1e3 * secs:.3f} ms "
            f"({100 * secs / red['device_s']:.2f}%)")
    if red["levels"]:
        log("rounds run by execution: "
            + " ".join(str(len(lv)) for lv in red["levels"]))
        log("ms by round, first to last (mean over executions): " + " ".join(
            f"{1e3 * s:.2f}" for s in scopes.level_table(red["levels"])))
    for name, secs in scopes.idle_by_annotation(
            ctx.get("trace"), red["host"])[:8]:
        log(f"idle under {name}: {secs:.4f} s")
    return red


def round_ms(ctx) -> float | None:
    """Median over the rounds of whole executions of one iteration of
    ``sssp.round`` (ms)."""
    scoped(ctx)
    return scopes.level_ms(ctx)


def scope_ms(ctx, prefix: str) -> float | None:
    """Self time per execution under scopes starting with ``prefix``
    (ms)."""
    scoped(ctx)
    return scopes.scope_ms(ctx, prefix)


def share(ctx) -> float | None:
    """Self time under the leaf scopes ``gather`` and ``fold`` over the
    program's device time (%)."""
    scoped(ctx)
    return scopes.share(ctx)


def rounds_per_batch(ctx=None) -> float | None:
    """Counter ``serve.sssp.rounds`` over ``serve.sssp.batches``: rounds
    of a served batch, the round that changed nothing included, mean
    over the batches the program ran (nothing is served before the
    window, so those are the window's and its drain's)."""
    from chipbench.parts import counter

    rounds, batches = (counter("serve.sssp.rounds"),
                       counter("serve.sssp.batches"))
    return rounds / batches if rounds is not None and batches else None
