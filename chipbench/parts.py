"""The program's own account of a served batch, for the readers of the
engine's parts and byte counters: per-request stage records
(``ctx["stages"]``) grouped into batches on the ONE monotonic clock the
records carry (``t0`` = ``perf_counter`` at admission; ``t0`` plus the
stage sums up to a mark is that mark's time), and the registry's
counters.  A program that records no ``t0``, no ``parts`` or no such
counter (the parent of the PR that added them) gives None everywhere.
"""

from __future__ import annotations

from chipbench.reading import median_ms


def batches(ctx) -> list[dict]:
    """One entry per executed batch, in pop order: ``t_pop`` (the worker
    took it), ``t_done`` (its last request was settled by the scatter
    pass), ``requests``, ``width`` and ``parts`` (seconds by part of
    ``execute``, or None).  Requests of a batch share their ``execute``
    seconds exactly (one pair of marks for the batch), which groups
    them.  [] when the records carry no ``t0``.  Grouped once a run
    (kept in ``ctx``)."""
    if "_batches" in ctx:
        return ctx["_batches"]
    groups = {}
    for rec in ctx.get("stages") or []:
        if "t0" not in rec or rec["labels"].get("status") != "ok":
            continue
        st = {s["stage"]: s for s in rec["stages"]}
        if "execute" not in st:
            continue
        key = (st["execute"]["s"], rec["labels"].get("width"))
        groups.setdefault(key, []).append((rec, st))
    out = []
    for (_, width), members in groups.items():
        rec, st = members[0]
        parts = st["execute"].get("parts")
        out.append({
            "t_pop": rec["t0"] + st.get("queue_wait", {"s": 0.0})["s"],
            "t_done": max(m[0]["t0"] + m[0]["wall_s"] for m in members),
            "requests": len(members),
            "width": width,
            "parts": (
                {p["stage"]: p["s"] for p in parts} if parts else None
            ),
        })
    ctx["_batches"] = sorted(out, key=lambda b: b["t_pop"])
    return ctx["_batches"]


def part_ms(ctx, part: str) -> float | None:
    """Median over batches of one part of ``execute`` (ms)."""
    return median_ms([
        b["parts"].get(part) for b in batches(ctx) if b["parts"]
    ])


def batch_gap_ms(ctx) -> float | None:
    """Median, over consecutive batches, of the time from the end of one
    batch's scatter pass to the worker's pop of the next (ms)."""
    bs = batches(ctx)
    return median_ms([
        b["t_pop"] - a["t_done"] for a, b in zip(bs, bs[1:])
    ])


def counter(name: str) -> int | None:
    """A program counter summed over its label sets; None where the
    program has no such series."""
    from combblas_tpu import obs

    values = [
        rec.get("value", 0) for rec in obs.registry.snapshot()
        if rec.get("name") == name and rec.get("kind") == "counter"
    ]
    return int(sum(values)) if values else None
