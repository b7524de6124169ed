"""Compiler / cache: JAX's own seconds for fetching programs from the
persistent cache, or compiling them, before the first send (the
``fetch`` and ``compile`` span events, as a union of their intervals;
the probe's apart, in ``boot_probe_s``) (s)."""

from chipbench import boot


def read(ctx):
    return boot.read(ctx, "boot_fetch_s")
