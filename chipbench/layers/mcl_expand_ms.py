"""Algorithms + local kernels: device time a job under the scope ``mcl.expand`` (a dense
iteration's row-block products on the matrix unit; a sparse iteration's sort-based product,
its own ``sq.*`` scopes inside): what a job pays to multiply (ms)."""

from chipbench import mclscopes


def read(ctx):
    return mclscopes.scope_ms(ctx, ("mcl.expand",))
