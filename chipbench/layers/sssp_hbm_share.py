"""Algorithms + local kernels: the least bytes one served kernel-3 batch must move
(``k3cost.sssp_batch_least_bytes``: computed, a lower bound) over the chip's peak
HBM bandwidth, over the measured device time of the batch program (%).  Bytes and
time are of the same batches: the rounds are those the profiled slice's whole
executions ran, counted in the trace.  Low means the sweep is nowhere near
memory-bound on what it has to move: it is bound by the gather's indices."""

from chipbench import cost, k3cost
from chipbench.k3scopes import scoped


def read(ctx):
    shape, red = ctx.get("sssp_cost"), scoped(ctx)
    if shape is None or not (red and red["levels"]):
        return None
    rounds = sum(len(lv) for lv in red["levels"]) / len(red["levels"])
    least = k3cost.sssp_batch_least_bytes(
        shape["n"], shape["slots"], shape["width"], rounds
    )
    peak = cost.peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * (least / peak) / red["device_s"]
