"""Set-up: ``setup_s`` minus the union of the program's top-level spans
before the first send: the interpreter, imports, the backend's start and
the benchmark's own preparation (s)."""

from chipbench import boot


def read(ctx):
    return boot.read(ctx, "boot_unspanned_s")
