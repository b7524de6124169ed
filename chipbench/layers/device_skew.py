"""Distributed ops: (max - min) / max of busy time across the devices used
(%)."""


def read(ctx):
    trace = ctx.get("trace")
    busy = [d["busy_s"] for d in trace["devices"].values()] if trace else []
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
