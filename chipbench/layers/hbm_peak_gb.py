"""Device: ``memory_stats()["peak_bytes_in_use"]`` on the fullest chip (GB):
what is left is room for wider lanes."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
