"""Engine: part ``readback`` of stage ``execute`` (``np.asarray`` of every
result block), median per batch (ms).  The traced batch has already waited for
the device (part ``device``), so this is the copy to the host alone."""

from chipbench.parts import part_ms


def read(ctx):
    return part_ms(ctx, "readback")
