"""Small helpers the per-layer readers share.  A reader is a file
``layers/<metric>.py`` with ``read(ctx) -> number | None``; ``ctx`` holds
what the driver observed: ``stages`` (per-request stage records),
``batches`` (per-batch facts), ``stats`` (``srv.stats()``), ``trace``
(the reduced profiler slice), ``late_s``, ``load_s``, ``warmup_s``,
``least_bytes``, ``device``, ``values``, ``compiles_in_window``.  A
reader that finds nothing to read returns None."""

from __future__ import annotations

import numpy as np


def median_ms(seconds) -> float | None:
    seconds = [s for s in seconds if s is not None]
    return 1e3 * float(np.median(seconds)) if len(seconds) else None


def stage_ms(ctx, stage: str) -> float | None:
    """Median over requests of one stage."""
    out = []
    for rec in ctx.get("stages") or []:
        out += [s["s"] for s in rec["stages"] if s["stage"] == stage]
    return median_ms(out)


def batch_ms(ctx, key: str) -> float | None:
    """Median over batches of one per-batch fact."""
    return median_ms([b[key] for b in ctx.get("batches") or []])


def device_ms(ctx) -> float | None:
    """Device time of one execution of the program that took most device
    time in the profiled slice."""
    from chipbench import devtrace

    trace = ctx.get("trace")
    dom = devtrace.dominant_module(trace) if trace else None
    return 1e3 * dom[2] if dom else None


def lane_fill(ctx) -> float | None:
    occ = (ctx.get("stats") or {}).get("mean_occupancy")
    return 100.0 * occ if occ is not None else None
