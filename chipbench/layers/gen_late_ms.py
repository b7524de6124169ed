"""Load generator: median of actual minus scheduled send (ms).  A starved
generator is not a fast server."""

from chipbench.reading import median_ms


def read(ctx):
    late = ctx.get("late_s")
    return median_ms(list(late)) if late is not None else None
