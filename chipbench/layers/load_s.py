"""Set-up: snapshot load, or build + upload on the first run (s)."""


def read(ctx):
    return ctx.get("load_s")
