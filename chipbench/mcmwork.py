"""A matching job as the program counts it (``models.mcm.*``, telemetry
on): phases, and how its steps were taken.  A program without the
counters (the parent of the PR that added the job) or a run without
telemetry gives None everywhere."""

from __future__ import annotations


def series(name: str, **labels) -> int | None:
    """Counter ``name`` summed over the label sets that hold
    ``labels``; None where the program has no such series."""
    from combblas_tpu import obs

    values = [
        rec.get("value", 0) for rec in obs.registry.snapshot()
        if rec.get("name") == name and rec.get("kind") == "counter"
        and all(rec.get("labels", {}).get(k) == v
                for k, v in labels.items())
    ]
    return int(sum(values)) if values else None


def phases_per_job(ctx=None) -> float | None:
    """``models.mcm.phases`` over ``models.mcm.jobs``: augmenting phases
    of a job, the one that finds nothing included (the warm-up job too:
    every job runs the same phases)."""
    phases, jobs = series("models.mcm.phases"), series("models.mcm.jobs")
    return phases / jobs if phases is not None and jobs else None


def push_share(ctx=None) -> float | None:
    """Steps taken as a walk of what is live over all steps (%): a
    round's two steps (``models.mcm.init_steps{mode}``) and a phase's
    layers (``models.mcm.layers{mode}``)."""
    walked = ran = 0
    for name in ("models.mcm.init_steps", "models.mcm.layers"):
        push, pull = series(name, mode="push"), series(name, mode="pull")
        if push is None or pull is None:
            return None
        walked, ran = walked + push, ran + push + pull
    return 100.0 * walked / ran if ran else None
