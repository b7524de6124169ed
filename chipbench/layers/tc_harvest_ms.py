"""Algorithms + local kernels: device time per job under the scope ``tc.harvest``: the
whole scan over chunks of row pairs, its ``gather`` and ``popcount`` steps and the
loop's own (ms)."""

from chipbench import tcscopes


def read(ctx):
    return tcscopes.scope_ms(ctx, ("tc.harvest",))
