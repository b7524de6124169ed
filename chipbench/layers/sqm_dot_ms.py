"""Algorithms + local kernels: the busiest device's time a job under the scopes
``sq.densify`` (a row block of A and a column panel of B of each stage scattered into
dense operands, the stage tiles' column-major sorts) and ``sq.dot`` (the stage products
on the matrix unit, two stages into each window's accumulator): what a chip pays to
multiply (ms)."""

from chipbench import sqmscopes


def read(ctx):
    return sqmscopes.scope_ms(ctx, ("sq.densify", "sq.dot"))
