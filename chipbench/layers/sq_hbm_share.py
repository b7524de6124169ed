"""Algorithms + local kernels: the least bytes one product job must move
(``sqcost.sq_job_least_bytes``: A read twice and C written once, 12 B an entry;
computed, the job's least work whatever implements it) over the chip's peak HBM
bandwidth, over the device's measured busy time inside a job (%): the product's share
of its roofline.  Low means the job moves, or computes, far more than its answer
holds: dense operands, a dense product, the passes of an extraction."""

from chipbench import cost
from chipbench.sqscopes import device_ms


def read(ctx):
    ms, least = device_ms(ctx), ctx.get("least_bytes")
    if ms is None or least is None:
        return None
    peak = cost.peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * (least / peak) / (ms * 1e-3)
