"""Set-up: wall of the program's span that made the served version
(``serve.restore``: snapshot to device; ``serve.load``: built on a first
run) plus ``serve.engine.init`` (s).  ``load_s`` less the benchmark's
own reading of the references' COO."""

from chipbench import boot


def read(ctx):
    return boot.read(ctx, "graph_ready_s")
