"""Algorithms + local kernels: device time of one iteration of the served BC
program's ``bc.forward`` loop (one whole plus-times sweep that counts shortest
paths into the next BFS level, and the state's update), median over the forward
sweeps of whole executions (ms)."""

from chipbench.bcscopes import sweep_ms


def read(ctx):
    return sweep_ms(ctx, "forward")
