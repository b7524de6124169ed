"""Algorithms + local kernels: device time a job under the scopes ``sq.densify`` (a row
block of A and a column panel of B scattered into dense operands; the sorted forms of a
tier that never densifies) and ``sq.dot`` (the stage products on the matrix unit; the
expansion of a tier that never densifies): what a job pays to multiply (ms)."""

from chipbench import sqscopes


def read(ctx):
    return sqscopes.scope_ms(ctx, ("sq.densify", "sq.dot"))
