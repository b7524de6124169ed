"""Engine / batcher scatter: bytes the scatter pass copied to hand each request
its own lane (counter ``serve.scatter.copied_bytes``), per batch (MB).  0 where
the lanes were handed out as views of the batch buffer (the program counts those
in ``serve.scatter.views``)."""

from chipbench.parts import batches, counter


def read(ctx):
    total, n = counter("serve.scatter.copied_bytes"), len(batches(ctx))
    return total / 1e6 / n if total is not None and n else None
