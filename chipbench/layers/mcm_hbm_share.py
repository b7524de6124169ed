"""Algorithms + local kernels: the least bytes one matching job must move
(``mcmcost.mcm_job_least_bytes``: every stored nonzero's index once and both mate vectors
once, from the graph alone: a lower bound) over the chip's peak HBM bandwidth, over the
measured device time of the job's program (%).  Low means the job is nowhere near
memory-bound on what it has to move: it is bound by its steps' fixed vector work and
serial scatters."""

from chipbench import cost
from chipbench.reading import device_ms


def read(ctx):
    ms, least = device_ms(ctx), ctx.get("least_bytes")
    if ms is None or least is None:
        return None
    peak = cost.peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * (least / peak) / (ms * 1e-3)
