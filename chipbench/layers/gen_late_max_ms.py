"""Load generator: the LARGEST actual minus scheduled send (ms).  The median
hides a single stall; one stall of seconds moves ``p95_ms`` by itself (seen on
the chip: 5.4 s and 6.1 s, PERF.md PR 22)."""


def read(ctx):
    late = ctx.get("late_s")
    return 1e3 * float(max(late)) if late is not None and len(late) else None
