"""Engine: part ``to_global`` of stage ``execute`` (reshape and slice of the
host blocks to ``[n, W]``, ``int(niter)``), median per batch (ms)."""

from chipbench.parts import part_ms


def read(ctx):
    return part_ms(ctx, "to_global")
