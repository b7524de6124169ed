"""Engine: stage ``execute`` (launch + device + ``[n, W]`` readback +
``to_global``), median per batch (ms)."""

from chipbench.reading import batch_ms


def read(ctx):
    return batch_ms(ctx, "execute_s")
