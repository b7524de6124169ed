"""Device time of the served BC program by ``jax.named_scope`` and by
sweep of each of its two loops, from the traced run's ``.xplane.pb``.

``scopes.py`` does the whole reduction (the dominant program's whole
executions, self time by scope, the iterations of the program's loop)
but fixes its pattern to the BFS names and reads ONE loop.  This
program's scopes (``combblas_tpu/models/bc.py:BC_SCOPES``) are four, two
of them loops: ``bc.init``, ``bc.forward`` (the ``while`` that counts
paths, one iteration a BFS level), ``bc.backward`` (the loop that
accumulates dependencies, one iteration a level back), ``bc.finish``;
``ell.bucket<i>`` and the leaf names are shared.  So the published
tables are handed to ``scopes.reduce_scopes`` twice, once with each loop
spelled as the one it knows, and what comes back is spelled as the
program spells it (the third such shim after ``k3scopes.py``: PERF.md
section 7).

Where the program publishes no table or the trace holds no scoped
operation (a program without these scopes; a CPU rehearsal, which has no
device plane), every reading is None, never 0.
"""

from __future__ import annotations

import statistics

from chipbench import scopes
from chipbench.deploy import log

FORWARD, BACKWARD = "bc.forward", "bc.backward"
#: each loop in turn takes ``scopes.LOOP``'s place; the other three names
#: take places ``scopes.SCOPES`` knows and no BC program uses
_REST = {"bc.init": "bfs.init", "bc.finish": "bfs.update"}
_AS_SCOPES_PY = {
    FORWARD: dict(_REST, **{FORWARD: scopes.LOOP, BACKWARD: "bfs.parents"}),
    BACKWARD: dict(_REST, **{BACKWARD: scopes.LOOP, FORWARD: "bfs.parents"}),
}


def _respell(path: str, names: dict) -> str:
    return "/".join(names.get(c, c) for c in path.split("/"))


def _reduce_as(source, tables: dict, loop: str) -> dict | None:
    names = _AS_SCOPES_PY[loop]
    red = scopes.reduce_scopes(source, {
        mod: {i: _respell(nm, names) for i, nm in table.items()}
        for mod, table in tables.items()
    })
    if red and red["by_scope"] is not None:
        back = {v: k for k, v in names.items()}
        red["by_scope"] = {
            _respell(lab, back): s for lab, s in red["by_scope"].items()
        }
    return red


def reduce_scopes(source, tables: dict) -> dict | None:
    """``scopes.reduce_scopes`` for a program under ``BC_SCOPES``:
    ``by_scope`` keyed ``bc.forward/ell.bucket3/gather``; ``levels`` the
    seconds of each forward sweep of each whole execution and
    ``backward`` those of each backward sweep (None where an execution
    ran none: a batch of roots without an edge)."""
    from jax.profiler import ProfileData

    # parsed once for the two readings
    if isinstance(source, (bytes, bytearray)):
        source = ProfileData.from_serialized_xspace(source)
    elif isinstance(source, str):
        source = ProfileData.from_file(source)
    red = _reduce_as(source, tables, FORWARD)
    if red is None:
        return None
    back = _reduce_as(source, tables, BACKWARD)
    red["backward"] = back["levels"] if back else None
    return red


def scoped(ctx) -> dict | None:
    """``reduce_scopes`` of this run's trace, once per run, kept in
    ``ctx`` where ``scopes.py``'s readers look for theirs (no BFS reader
    runs in a cell of this kind), so ``scopes.scope_ms`` / ``share`` read
    it as it is; logs the table by phase and by sweep the first time."""
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
    for title, key in (("forward", "levels"), ("backward", "backward")):
        if red[key]:
            log(f"{title} sweeps run by execution: "
                + " ".join(str(len(lv)) for lv in red[key]))
            log(f"ms by {title} sweep, first to last (mean over "
                "executions): " + " ".join(
                    f"{1e3 * s:.2f}" for s in scopes.level_table(red[key])))
    for name, secs in scopes.idle_by_annotation(
            ctx.get("trace"), red["host"])[:8]:
        log(f"idle under {name}: {secs:.4f} s")
    return red


def sweep_ms(ctx, phase: str) -> float | None:
    """Median over the whole executions' iterations of one loop
    (``"forward"`` / ``"backward"``) of one sweep (ms)."""
    red = scoped(ctx)
    key = {"forward": "levels", "backward": "backward"}[phase]
    flat = [s for lv in (red or {}).get(key) or [] for s in lv]
    return 1e3 * statistics.median(flat) if flat else None


def sweeps_run(ctx) -> tuple[float, float] | None:
    """Forward and backward sweeps of one whole execution of the slice,
    counted in the trace (means over the executions)."""
    red = scoped(ctx)
    if not red or not red.get("levels") or not red.get("backward"):
        return None
    return tuple(
        sum(len(lv) for lv in red[key]) / len(red[key])
        for key in ("levels", "backward")
    )


def share(ctx) -> float | None:
    """Self time under the leaf scopes ``gather`` and ``fold`` (both
    loops) over the program's device time (%)."""
    scoped(ctx)
    return scopes.share(ctx)


def sweeps_per_batch(ctx=None) -> float | None:
    """Counter ``serve.bc.sweeps`` (both phases) over
    ``serve.bc.batches``: whole sweeps of the matrix a served batch ran,
    mean over the batches the program ran (nothing is served before the
    window, so those are the window's and its drain's)."""
    from chipbench.parts import counter

    sweeps, batches = counter("serve.bc.sweeps"), counter("serve.bc.batches")
    return sweeps / batches if sweeps is not None and batches else None
