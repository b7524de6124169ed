"""Algorithms + local kernels: the least bytes one FastSV job must move
(``cccost.cc_job_least_bytes`` on the rounds and jumps every job of the run returned:
computed, a lower bound) over the chip's peak HBM bandwidth, over the measured device
time of the job's program (%): the one-lane ELL sweep's share of its roofline.  Low
means the sweep is nowhere near memory-bound on what it has to move: it is bound by
the gather's indices."""

from chipbench import cost
from chipbench.reading import device_ms


def read(ctx):
    ms, least = device_ms(ctx), ctx.get("least_bytes")
    if ms is None or least is None:
        return None
    peak = cost.peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * (least / peak) / (ms * 1e-3)
