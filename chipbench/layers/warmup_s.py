"""Set-up: warming this cell's own programs (s)."""


def read(ctx):
    return ctx.get("warmup_s")
