"""Algorithms + local kernels: the least bytes the fullest chip must move in one mesh
job (``sqmcost.sq_mesh_job_least_bytes``: A's row block and column block read once and
its tile of C written once, 12 B an entry, from the REFERENCE's counts; the job's least
work whatever implements it) over one chip's peak HBM bandwidth, over the busiest
device's measured busy time inside a job (%): the product's share of its roofline on
the mesh.  It cannot pass 100%."""

from chipbench import cost
from chipbench.sqmscopes import device_ms


def read(ctx):
    ms, least = device_ms(ctx), ctx.get("least_bytes")
    if ms is None or least is None:
        return None
    peak = cost.peaks(ctx["device"]["kind"])["hbm_gbps"] * 1e9
    return 100.0 * (least / peak) / (ms * 1e-3)
