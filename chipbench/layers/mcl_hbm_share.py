"""Algorithms + local kernels: the least bytes one clustering job must move
(``mclcost.mcl_job_least_bytes``: every iteration reads its operand twice and writes what
it keeps, 12 B an entry, from the reference's counts; the job's least work whatever
implements it) over the chip's peak HBM bandwidth, over the device's measured busy time
inside a job (%): the job's share of its roofline.  Low means the job moves, or computes,
far more than its answers hold: a dense state, n^3 multiply-adds, the passes of a select."""

from chipbench import cost
from chipbench.mclscopes import device_ms


def read(ctx):
    ms, least = device_ms(ctx), ctx.get("least_bytes")
    if ms is None or least is None:
        return None
    peak = cost.peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * (least / peak) / (ms * 1e-3)
