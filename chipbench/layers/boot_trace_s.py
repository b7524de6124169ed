"""Compiler / cache: JAX's own seconds for tracing and lowering before
the first send (the ``trace`` and ``lower`` span events, as a union of
their intervals; the probe's apart, in ``boot_probe_s``) (s)."""

from chipbench import boot


def read(ctx):
    return boot.read(ctx, "boot_trace_s")
