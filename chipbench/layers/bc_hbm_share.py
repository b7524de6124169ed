"""Algorithms + local kernels: the least bytes one served BC batch must move
(``bccost.bc_batch_least_bytes``: computed, a lower bound) over the chip's peak HBM
bandwidth, over the measured device time of the batch program (%): the shared ELL
sweep's share of its roofline under this payload (f32 values, plus-times).  Bytes
and time are of the same batches: the sweeps are those the profiled slice's whole
executions ran, counted in the trace.  Low means the sweep is nowhere near
memory-bound on what it has to move: it is bound by the gather's indices."""

from chipbench import bccost, cost
from chipbench.bcscopes import scoped, sweeps_run


def read(ctx):
    shape, red, run = ctx.get("bc_cost"), scoped(ctx), sweeps_run(ctx)
    if shape is None or run is None:
        return None
    least = bccost.bc_batch_least_bytes(
        shape["slots"], shape["n"], shape["width"], *run
    )
    peak = cost.peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * (least / peak) / red["device_s"]
