"""Algorithms + local kernels: device time under the scope ``sssp.parents`` (the one
sweep after the fixed point that picks every reached row's parent) per execution
of the served kernel-3 program (ms)."""

from chipbench.k3scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "sssp.parents")
