"""Algorithms + local kernels: self time of the FastSV program under the scopes
``cc.hook`` (the scatter-min of ``n`` labels into ``n`` slots) and ``cc.gather``
(``f[f]``), the two vector subscripts of a round, over its device time, whole
executions of the profiled slice (%)."""

from chipbench import ccscopes


def read(ctx):
    return ccscopes.share(ctx, ("cc.hook", "cc.gather"))
