"""Distributed ops: time in collective operations over device busy time,
mean of the devices used (%)."""


def read(ctx):
    trace = ctx.get("trace")
    devs = list(trace["devices"].values()) if trace else []
    shares = [
        d["collective_s"] / d["busy_s"] for d in devs if d["busy_s"] > 0
    ]
    if len(devs) < 2 or not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
