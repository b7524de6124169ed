"""Device time of the triangle-count program by ``jax.named_scope``,
from the traced run's ``.xplane.pb``.

``scopes.py`` does the whole reduction (the dominant program's whole
executions, self time by scope, the iterations of the program's loop)
but fixes its pattern and its loop to the BFS names.  This program's
scopes (``combblas_tpu/models/tc.py:TC_SCOPES``) are five: ``tc.dedup``,
``tc.pack``, ``tc.harvest`` (the scan whose iteration is one chunk of
row pairs) and inside a step ``gather`` and ``popcount``; ``gather`` is
a name ``scopes.py`` knows.  So the published tables are handed to
``scopes.reduce_scopes`` with ``tc.harvest`` spelled as the loop it
knows and the three others as degree classes no matrix has, as
``ccscopes.py`` does, and what comes back is spelled as the program
spells it (the fifth such shim: PERF.md section 7).

Where the program publishes no table or the trace holds no scoped
operation (a program without these scopes; a CPU rehearsal, which has no
device plane), every reading is None, never 0.
"""

from __future__ import annotations

import statistics

from chipbench import scopes
from chipbench.deploy import log

LOOP = "tc.harvest"
_REST = ("tc.dedup", "tc.pack", "popcount")
_AS_SCOPES_PY = dict(
    {LOOP: scopes.LOOP},
    **{name: f"ell.bucket{9100 + k}" for k, name in enumerate(_REST)},
)
_AS_PROGRAM = {v: k for k, v in _AS_SCOPES_PY.items()}


def _respell(path: str, names: dict) -> str:
    return "/".join(names.get(c, c) for c in path.split("/"))


def reduce_scopes(source, tables: dict) -> dict | None:
    """``scopes.reduce_scopes`` for a program under ``TC_SCOPES``:
    ``by_scope`` keyed ``tc.pack``, ``tc.harvest/gather`` ...,
    ``levels`` the seconds of each step of each whole execution's
    scan."""
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
    runs in a cell of this kind); logs the table by scope, the scan's
    steps and the bytes a second its gathers sustain the first time."""
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
    table = dict(red["by_scope"], **{"<none>": red["unscoped_s"]})
    for lab, secs in sorted(table.items(), key=lambda kv: -kv[1]):
        log(f"scope {lab}: {1e3 * secs:.3f} ms "
            f"({100 * secs / red['device_s']:.2f}%)")
    steps = [s for lv in red["levels"] or [] for s in lv]
    if steps:
        log("steps of the scan by execution: "
            + " ".join(str(len(lv)) for lv in red["levels"])
            + f"; a step {1e3 * statistics.median(steps):.3f} ms (median)")
    harvest, moved = scope_ms(ctx, (LOOP,)), ctx.get("gathered_bytes")
    if harvest and moved:
        log(f"the scan gathers {moved / 1e9:.1f} GB a job: "
            f"{moved / harvest / 1e6:.1f} GB/s over {harvest:.1f} ms")
    return red


def scope_ms(ctx, under: tuple) -> float | None:
    """Self time per execution under the scopes whose path starts with
    one of ``under`` (ms)."""
    red = scoped(ctx)
    if not red or red["by_scope"] is None:
        return None
    hit = [v for k, v in red["by_scope"].items()
           if k.split("/")[0] in under]
    return 1e3 * sum(hit) if hit else None
