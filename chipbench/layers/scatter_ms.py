"""Engine / batcher scatter: the largest ``scatter`` stage within each batch
(the whole scatter pass), median over batches (ms)."""

from chipbench.reading import batch_ms


def read(ctx):
    return batch_ms(ctx, "scatter_s")
