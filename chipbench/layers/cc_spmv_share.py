"""Algorithms + local kernels: self time of the FastSV program under the scope
``cc.spmv`` (the one-lane sweep: its table, every class's gather, fold and
scatter_rows) over its device time, whole executions of the profiled slice (%)."""

from chipbench import ccscopes


def read(ctx):
    return ccscopes.share(ctx, ("cc.spmv",))
