"""A deep search's levels as the program counts them, and the work the
REFERENCE says its waves did.

The program annotates a served BFS batch's stage record with ``levels``
(the batch's ``niter``: the levels of its deepest lane and the one that
finds nothing), ``push_levels`` (those it took as a walk of the
frontier's columns, ``serve.bfs.levels{mode=push}``) and ``push_edges``
(the edges those walks held, ``serve.bfs.push_edges``).  A program
without them (the parent of the PR that added the push to the loop), a
run without telemetry or a trace without scopes gives None everywhere.

``ctx["deep"]`` is the driver's (``drivers/serve_closed_deep.py``), from
scipy alone: ``edges_per_query`` / ``vertices_per_query``, the directed
edges and the vertices of a drawn root's component, mean over the
window's roots.
"""

from __future__ import annotations

import statistics

from chipbench import deepscopes, scopes
from chipbench.reading import device_ms


def batches(ctx) -> list[dict]:
    """One entry a served batch whose stage records carry its levels
    (``ellwork.batches``' grouping: requests of a batch share their
    ``execute`` seconds exactly)."""
    if "_deep_batches" in ctx:
        return ctx["_deep_batches"]
    groups = {}
    for rec in ctx.get("stages") or []:
        lab = rec.get("labels", {})
        if lab.get("status") != "ok" or "levels" not in lab:
            continue
        for s in rec["stages"]:
            if s["stage"] == "execute":
                groups.setdefault((s["s"], lab.get("width")), []).append(lab)
    out = [{
        "width": width, "requests": len(labs), "levels": labs[0]["levels"],
        "push_levels": labs[0]["push_levels"],
        "push_edges": labs[0]["push_edges"],
    } for (_, width), labs in groups.items()]
    ctx["_deep_batches"] = out
    return out


def widest(ctx) -> list[dict]:
    """The batches of the widest lane: the waves of a closed loop that
    keeps every lane full, and the program the trace's dominant module
    is."""
    bs = batches(ctx)
    width = max((b["width"] for b in bs), default=None)
    return [b for b in bs if b["width"] == width]


def levels(ctx) -> float | None:
    """Levels a wave ran, mean over the widest lane's batches."""
    bs = widest(ctx)
    return statistics.fmean(b["levels"] for b in bs) if bs else None


def push_share(ctx) -> float | None:
    """Levels taken by the frontier-proportional step over levels run
    (%), all batches."""
    bs = batches(ctx)
    ran = sum(b["levels"] for b in bs)
    return 100.0 * sum(b["push_levels"] for b in bs) / ran if ran else None


def level_us(ctx) -> float | None:
    """Device time under ``bfs.level`` (the loop, ``bfs.push`` inside it
    included) of one execution of the wave's program over the levels a
    wave ran (us)."""
    deepscopes.log_breakdown(ctx)
    ms, ran = scopes.scope_ms(ctx, scopes.LOOP), levels(ctx)
    return 1e3 * ms / ran if ms is not None and ran else None


def _wave(ctx, key: str) -> float | None:
    """``ctx["deep"][key]`` a query times the queries of a wave."""
    per_query = (ctx.get("deep") or {}).get(key)
    bs = widest(ctx)
    if per_query is None or not bs:
        return None
    return per_query * statistics.fmean(b["requests"] for b in bs)


def ns_per_edge(ctx) -> float | None:
    """A wave's device time over the directed edges its lanes' searches
    cross, the reference's count (ns)."""
    ms, edges = device_ms(ctx), _wave(ctx, "edges_per_query")
    return 1e6 * ms / edges if ms is not None and edges else None


def hbm_share(ctx) -> float | None:
    """The least bytes a wave's searches must move
    (``deepcost.bfs_search_least_bytes``) over the chip's peak HBM
    bandwidth, over the wave's device time (%)."""
    from chipbench import cost, deepcost

    ms = device_ms(ctx)
    edges, vertices = (_wave(ctx, k + "_per_query")
                       for k in ("edges", "vertices"))
    if ms is None or not edges or not vertices:
        return None
    peak = cost.peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    least = deepcost.bfs_search_least_bytes(edges, vertices)
    return 100.0 * (least / peak) / (ms * 1e-3)
