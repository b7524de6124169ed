"""Distributed ops: time in collective operations over device busy time INSIDE whole
product jobs, mean of the devices used (%): ``collective_share``'s arithmetic on what
``sqmscopes.py`` cuts out of the trace job by job (the stage exchange, the symbolic
passes' gathers, the pack's and the digest's reductions; a collective's time holds its
wait for the slowest chip)."""

from chipbench import sqmscopes


def read(ctx):
    red = sqmscopes.scoped(ctx)
    devs = list(red["devices"].values()) if red else []
    shares = [
        d["collective_s"] / d["device_s"] for d in devs if d["device_s"] > 0
    ]
    if len(devs) < 2 or not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
