"""Engine: part ``launch`` of stage ``execute`` (``jnp.asarray`` of the
sources and dispatch, until the plan returns), median per batch (ms)."""

from chipbench.parts import part_ms


def read(ctx):
    return part_ms(ctx, "launch")
