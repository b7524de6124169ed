"""Set-up: the ``upload`` and ``companion`` children of the span that
made the served version: host arrays to the device, blocked on (s)."""

from chipbench import boot


def read(ctx):
    return boot.read(ctx, "upload_s")
