"""Engine: bytes read back from the device (counter ``serve.readback.bytes``,
counted at the ``np.asarray``) over the requests completed (MB).  Nothing is
served before the window, so the counter's total is the window's and its drain's."""

from chipbench.parts import batches, counter


def read(ctx):
    total = counter("serve.readback.bytes")
    requests = sum(b["requests"] for b in batches(ctx))
    return total / 1e6 / requests if total is not None and requests else None
