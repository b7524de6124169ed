"""Algorithms + local kernels: the least bytes one triangle-count job must move
(``tccost.tc_job_least_bytes``: the bit table written once and read once, the edge
list read twice; computed, a lower bound that charges no row a pair re-fetches) over
the chip's peak HBM bandwidth, over the measured device time of the job's program
(%): the pack-and-harvest kernel's share of its roofline.  Low means the job moves
far more than it has to: two whole rows a pair."""

from chipbench import cost
from chipbench.reading import device_ms


def read(ctx):
    ms, least = device_ms(ctx), ctx.get("least_bytes")
    if ms is None or least is None:
        return None
    peak = cost.peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * (least / peak) / (ms * 1e-3)
