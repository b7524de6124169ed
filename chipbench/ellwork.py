"""The work of the per-degree-class ELL sweep, as the program counts it,
and what an index of it costs.

The program tallies, where the class loop makes its choice
(``combblas_tpu/parallel/ellmat.py:_ell_class_sweeps``), how often each
degree class of each tile was swept dense or skipped, and weighs the
counts on the host by the class's slots (padded ones included: the device
gathers them).  It records the result three ways, each with ONE use here:

- the labels ``slots`` and ``slots_skipped`` (the BUSIEST tile's: a wave
  waits for it, and one chip has one tile) of a served request's stage
  record (``ctx["stages"]``): its batch's own work, beside the ``device``
  part of the batch's ``execute`` on the same clock.  Every number of a
  served cell comes from these (``batches``);
- counters ``ell.class_sweeps`` / ``ell.slots`` ``{kind, width, cls,
  mode}`` (``phase`` too for "bc") and ``ell.batches{kind, width}``:
  sweeps by number over all tiles, slots of the busiest tile.  The
  by-class table of a traced run is made of them (``log_by_class``), and
  a FastSV job's numbers (``kind`` "cc", ``width`` 1, nothing ever
  skipped): a library job has no stage record;
- the device trace's ``ell.bucket<i>/gather`` and ``/fold`` scopes, which
  the by-scope tables of ``scopes`` / ``k3scopes`` / ``bcscopes`` /
  ``ccscopes`` keep under the loop that ran them.

Nanoseconds an index are those scopes' self time INSIDE the tallied loops
(``LOOPS``) over the slots the tally says were gathered; the parents pass
of kernel 3 (``sssp.parents``) sweeps outside the tally and is left out of
both.  A program without the family (the parent of the PR that added it),
a run without telemetry or a trace without scopes gives None everywhere.
"""

from __future__ import annotations

import re
import statistics

from chipbench.deploy import log

#: the loops whose sweeps the program tallies, by served kind (and FastSV)
LOOPS = {
    "bfs": ("bfs.level",),
    "sssp": ("sssp.round",),
    "bc": ("bc.forward", "bc.backward"),
    "cc": ("cc.spmv",),
}
LEAVES = ("gather", "fold")


def counters(name: str, **labels) -> list[dict]:
    """The registry's counter series ``name`` whose labels hold
    ``labels``; [] where the program has none."""
    from combblas_tpu import obs

    return [
        rec for rec in obs.registry.snapshot()
        if rec.get("kind") == "counter" and rec.get("name") == name
        and labels.items() <= rec.get("labels", {}).items()
    ]


def total(name: str, **labels) -> int | None:
    """Those series added up; None where there is none."""
    found = counters(name, **labels)
    return int(sum(rec["value"] for rec in found)) if found else None


def kind_of(ctx) -> str | None:
    """The served kind of the cell's mix (its ``kind`` label in the
    family)."""
    return (ctx.get("mix") or {}).get("kind")


def log_by_class(ctx, kind: str | None = None) -> None:
    """Once a run: the family's counters of ``kind`` (the mix's served
    kind where none is given) class by class: the busiest tile's slots
    of one dense sweep, sweeps run dense and skipped over all tiles, and
    the share of the gathered slots the class holds."""
    if ctx.get("_ell_by_class"):
        return
    ctx["_ell_by_class"] = True
    kind = kind or kind_of(ctx)
    tiles = max(int((ctx.get("device") or {}).get("count", 1)), 1)
    rows = {}
    for name in ("ell.class_sweeps", "ell.slots"):
        for rec in counters(name, kind=kind):
            lab = rec["labels"]
            row = rows.setdefault(lab["cls"], {})
            key = (name, lab["mode"])
            row[key] = row.get(key, 0) + rec["value"]
    gathered = sum(r.get(("ell.slots", "dense"), 0) for r in rows.values())
    if not gathered:
        return
    log(f"ell work of kind {kind} by degree class, "
        f"{total('ell.batches', kind=kind)} batches of all widths (slots "
        "of one dense sweep of the busiest tile; sweeps over all tiles):")
    for cls in sorted(rows):
        r = rows[cls]
        dense, skipped = (r.get(("ell.class_sweeps", m), 0)
                          for m in ("dense", "skipped"))
        slots = sum(r.get(("ell.slots", m), 0) for m in ("dense", "skipped"))
        per_sweep = slots * tiles // max(dense + skipped, 1)
        log(f"class {cls}: slots {per_sweep}, dense {dense}, skipped "
            f"{skipped}, {100 * r.get(('ell.slots', 'dense'), 0) / gathered:.2f}"
            "% of the gathered")


# --- a served cell: the stage records --------------------------------------


def batches(ctx) -> list[dict]:
    """One entry a served batch whose stage records carry its work:
    ``width``, ``requests``, ``slots``, ``slots_skipped`` and ``device_s``
    (the part ``device`` of its ``execute``, None where the records have
    no parts).  Requests of a batch share their ``execute`` seconds
    exactly, which groups them (``parts.batches``' rule).  [] where the
    program annotates no ``slots``."""
    if "_ell_batches" in ctx:
        return ctx["_ell_batches"]
    groups = {}
    for rec in ctx.get("stages") or []:
        lab = rec.get("labels", {})
        if lab.get("status") != "ok" or "slots" not in lab:
            continue
        st = {s["stage"]: s for s in rec["stages"]}
        if "execute" in st:
            groups.setdefault(
                (st["execute"]["s"], lab.get("width")), []
            ).append((lab, st["execute"]))
    out = []
    for (_, width), members in groups.items():
        lab, execute = members[0]
        parts = {p["stage"]: p["s"] for p in execute.get("parts") or []}
        out.append({
            "width": width, "requests": len(members),
            "slots": lab["slots"], "slots_skipped": lab["slots_skipped"],
            "device_s": parts.get("device"),
        })
    ctx["_ell_batches"] = out
    return out


def dominant(ctx) -> list[dict]:
    """The batches of the lane width whose batches gathered most slots
    together: the program that did most of the window's work (all of
    it, in a closed loop that keeps every lane full)."""
    by_width = {}
    for b in batches(ctx):
        by_width.setdefault(b["width"], []).append(b)
    return max(by_width.values(), default=[],
               key=lambda bs: sum(b["slots"] for b in bs))


def mslots_per_batch(ctx, kind: str | None = None) -> float | None:
    """Slots a batch gathered, the busiest tile's, in millions: mean over
    the ``dominant`` batches' stage records; of kind "cc" (a library job
    for a batch, no stage record) ``ell.slots{mode=dense}`` over
    ``ell.batches``."""
    if kind == "cc":
        slots = total("ell.slots", kind="cc", mode="dense")
        jobs = total("ell.batches", kind="cc")
        return slots / jobs / 1e6 if slots is not None and jobs else None
    bs = dominant(ctx)
    return statistics.fmean(b["slots"] for b in bs) / 1e6 if bs else None


def skipped_share(ctx) -> float | None:
    """Slots the ``dominant`` batches skipped over all the slots their
    sweeps stood before, the busiest tile's (%)."""
    bs = dominant(ctx)
    dense = sum(b["slots"] for b in bs)
    skipped = sum(b["slots_skipped"] for b in bs)
    return 100.0 * skipped / (dense + skipped) if dense + skipped else None


def mslots_per_query(ctx) -> float | None:
    """Slots gathered by the window's batches over the requests they
    answered, in millions."""
    bs = batches(ctx)
    requests = sum(b["requests"] for b in bs)
    return sum(b["slots"] for b in bs) / requests / 1e6 if requests else None


def wave_ns_per_slot(ctx) -> float | None:
    """Median over batches of the batch's ``device`` part of ``execute``
    over its own gathered slots (ns); logs the same by width the first
    time."""
    waves = [b for b in batches(ctx) if b["device_s"] and b["slots"]]
    if not waves:
        return None
    if not ctx.get("_ell_by_width"):
        ctx["_ell_by_width"] = True
        by_width = {}
        for b in waves:
            by_width.setdefault(b["width"], []).append(b)
        for width in sorted(by_width, key=lambda w: (w is None, w)):
            ws = by_width[width]
            log(f"waves of width {width}: {len(ws)}, median wave "
                f"{1e3 * statistics.median(b['device_s'] for b in ws):.1f} "
                "ms, median "
                f"{statistics.median(b['slots'] for b in ws) / 1e6:.2f} "
                "Mslots, median "
                f"{statistics.median(1e9 * b['device_s'] / b['slots'] for b in ws):.3f}"
                " ns a slot")
    return statistics.median(1e9 * b["device_s"] / b["slots"] for b in waves)


# --- the device trace's account --------------------------------------------


def _scoped(ctx, kind):
    """The by-scope reduction of this run's trace, by the reducer of the
    cell's kind (each keeps it in ``ctx`` for the kind's other readers)."""
    from chipbench import bcscopes, ccscopes, k3scopes, scopes

    reducer = {"bfs": scopes, "sssp": k3scopes, "bc": bcscopes,
               "cc": ccscopes}.get(kind)
    return reducer.scoped(ctx) if reducer else None


def sweep_seconds(red, kind) -> float | None:
    """Self time of one execution under ``ell.bucket<i>/gather`` and
    ``/fold`` inside the loops the program tallies (``LOOPS[kind]``),
    mean over the executions the trace holds."""
    if not red or red.get("by_scope") is None:
        return None
    hit = [
        secs for lab, secs in red["by_scope"].items()
        if lab.rsplit("/", 1)[-1] in LEAVES
        and set(lab.split("/")) & set(LOOPS[kind])
    ]
    return sum(hit) if hit else None


def ns_per_index(ctx) -> float | None:
    """A served cell on ONE chip: ``sweep_seconds`` of the dominant
    program's execution over the ``dominant`` batches' mean ``slots``
    (ns), so that ``mslots_per_batch`` x this = the by-scope log's
    milliseconds.  None on a mesh (the seconds are a mean over its device
    planes, the records' slots the busiest tile's: no cell of one is
    listed), and where the trace's dominant program is not the records'
    dominant width."""
    kind = kind_of(ctx)
    if kind not in LOOPS or (ctx.get("device") or {}).get("count", 1) > 1:
        return None
    red = _scoped(ctx, kind)
    seconds = sweep_seconds(red, kind)
    bs = dominant(ctx)
    if seconds is None or not bs:
        return None
    if not (red.get("module") or "").endswith(f"_w{bs[0]['width']}"):
        log(f"ell work: the trace's {red.get('module')} is not the "
            f"records' width {bs[0]['width']}")
        return None
    mean = statistics.fmean(b["slots"] for b in bs)
    if not mean:
        return None
    log(f"ell work: {red['module']}, {len(bs)} batches of "
        f"{mean / 1e6:.3f} Mslots (mean), gather + fold in the tallied "
        f"loops {1e3 * seconds:.3f} ms an execution, "
        f"{1e9 * seconds / mean:.3f} ns an index")
    return 1e9 * seconds / mean


def cc_ns_per_index(ctx) -> float | None:
    """A FastSV job: ``ccscopes``' gather + fold seconds an execution
    over the job's gathered slots (ns)."""
    mslots = mslots_per_batch(ctx, "cc")
    seconds = sweep_seconds(_scoped(ctx, "cc"), "cc")
    if seconds is None or not mslots:
        return None
    return 1e3 * seconds / mslots
