"""Algorithms + local kernels: device time under the scope ``bfs.parents`` (the
one pass that rebuilds parents from levels) per execution of the
``bfs_batch_compact`` program (ms)."""

from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "bfs.parents")
