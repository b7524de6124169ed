"""Distributed ops: (max - min) / max of the busy time inside whole product jobs across
the devices used (%): ``device_skew``'s arithmetic on ``sqmscopes.py``'s per-device
times.  Capacities are SPMD-uniform (the heaviest tile sizes all four), so the skew is
what the tiles' unequal CONTENTS cost, not their shapes."""

from chipbench import sqmscopes


def read(ctx):
    red = sqmscopes.scoped(ctx)
    busy = [d["device_s"] for d in red["devices"].values()] if red else []
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
