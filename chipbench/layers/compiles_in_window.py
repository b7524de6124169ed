"""Compiler / cache: programs traced, compiled or fetched from the
persistent cache inside the window (the program's ``trace.serve`` and
``compile_cache.misses`` counters, and JAX's own compile events).  Must be 0:
whatever compiles in the window was left out of set-up."""


def read(ctx):
    return ctx.get("compiles_in_window")
