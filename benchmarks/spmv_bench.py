"""SpMSpV / SpMV kernel microbenchmark (≈ Applications/SpMSpV-IPDPS2017).

Compares the COO segment-reduce SpMV against the bucketed sliced-ELL path
on one chip, with the same protocol as bench.py (host build, one
upload, batched launches, one barrier readback). Prints one JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

SCALE = int(os.environ.get("BENCH_SCALE", "18"))
REPS = int(os.environ.get("BENCH_REPS", "8"))
LADDER = os.environ.get("BENCH_LADDER", "fine")  # fine | coarse (1-lane
# payloads favor coarse: fewer bucket classes, see _width_ladder)


def main():
    import jax
    import numpy as np

    from combblas_tpu import PLUS_TIMES, SELECT2ND_MAX
    from combblas_tpu.parallel.ellmat import EllParMat
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmv import dist_spmv
    from combblas_tpu.parallel.vec import DistVec
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    grid = Grid.make(1, 1)
    n = 1 << SCALE
    rows, cols = rmat_symmetric_coo_host(3, SCALE, 16)
    key = rows * np.int64(n) + cols
    uniq = np.unique(key)
    ru, cu = uniq // n, uniq % n
    E = EllParMat.from_host_coo(
        grid, ru, cu, np.ones(len(ru), np.float32), n, n, ladder=LADDER
    )
    x = DistVec.from_global(
        grid, np.random.default_rng(0).random(n).astype(np.float32),
        align="col",
    )

    # All REPS chained inside ONE launch, so per-launch dispatch cannot
    # swamp the kernel (it measured ~105 ms-1.8 s in rounds 2-5 on a
    # machine that is gone; not re-measured).
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def chain(ell, x0):
        # ell passed as an ARGUMENT: a closure would embed the bucket
        # arrays as HLO constants in the compiled program.
        def body(_, xb):
            xv = DistVec(blocks=xb, length=n, align="col", grid=grid)
            y = dist_spmv(PLUS_TIMES, ell, xv)
            return y.realign("col").blocks

        return lax.fori_loop(0, REPS, body, x0)

    out = chain(E, x.blocks)  # warmup/compile
    jax.block_until_ready(out)
    time.sleep(3)
    t0 = time.perf_counter()
    out = chain(E, x.blocks)
    _ = float(jax.device_get(out[0, 0]))  # barrier
    dt = time.perf_counter() - t0
    gflops = len(ru) * 2 * REPS / dt / 1e9
    ell_bytes = sum(
        bc.size * 4 + bv.size * 4 + br.size * 4 for bc, bv, br in E.buckets
    )
    print(
        json.dumps(
            {
                "metric": f"spmv_ell{LADDER}_rmat_scale{SCALE}_chained_GFLOPs",
                "value": round(gflops, 3),
                "unit": "GFLOP/s",
                "nnz": int(len(ru)),
                "reps": REPS,
                "ms_per_spmv": round(dt / REPS * 1e3, 2),
                "achieved_GBps": round(ell_bytes * REPS / dt / 1e9, 2),
            }
        )
    )


if __name__ == "__main__":
    main()
