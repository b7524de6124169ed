"""Algorithms + local kernels: device time of one iteration of the served BC
program's ``bc.backward`` loop (one whole plus-times sweep that pulls
``(1 + delta) / sigma`` back one level, and the update of ``delta``), median over
the backward sweeps of whole executions (ms)."""

from chipbench.bcscopes import sweep_ms


def read(ctx):
    return sweep_ms(ctx, "backward")
