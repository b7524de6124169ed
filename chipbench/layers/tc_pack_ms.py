"""Algorithms + local kernels: device time per job under the scopes ``tc.dedup`` (two
stable sorts of every stored slot and the repeat mask) and ``tc.pack`` (the zero fill
of the ``uint32[n, n/32]`` table and the scatter-add of one bit a kept nonzero): what
a job pays before its first pair (ms)."""

from chipbench import tcscopes


def read(ctx):
    return tcscopes.scope_ms(ctx, ("tc.dedup", "tc.pack"))
