"""Set-up: what the traced run's boot pays for being traced: the
``probe`` parts of the ``serve.warmup`` spans and the top-level
``obs.opnames.publish`` spans (lowering again, the second fetch, the
program's text, its parse) (s).  An untraced boot pays none of it."""

from chipbench import boot


def read(ctx):
    return boot.read(ctx, "boot_probe_s")
