"""Algorithms + local kernels: the least bytes one batch must move
(``cost.bfs_batch_least_bytes``: computed, a lower bound) over the chip's peak
HBM bandwidth, over the measured device time of the batch program (%).  Low
means the kernel is nowhere near memory-bound on what it has to move."""

from chipbench import cost
from chipbench.reading import device_ms


def read(ctx):
    ms, least = device_ms(ctx), ctx.get("least_bytes")
    if ms is None or least is None:
        return None
    peak = cost.peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * (least / peak) / (ms * 1e-3)
