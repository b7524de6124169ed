#!/usr/bin/env python
"""Where a product leaves the dense tier, on the other caller of the
rule's line (``parallel/spgemm.py:WINDOWED_MAX_CELLS_PER_FLOP``): one
warm ``spgemm_job(PLUS_TIMES, A, A, tier=...)`` under ``windowed`` and
under ``scan`` on the MCL cell's graph thinned to a few multiplies a
cell (``chipbench/famgraph.py`` at a lower ``--degrees``), the cell's
n = 2^14.  ROADMAP D4's table; ``scripts/mcl_loops.py --cells-per-flop``
is the clustering job's half.

    chiprun -- python scripts/tier_line.py
    JAX_PLATFORMS=cpu python scripts/tier_line.py --scale 9 --smax 96 --degrees 6 3

One process, a cold call and three warm ones a (graph, tier); one JSON
line each on stdout and in ``chiprun_out/tier_line.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join("chiprun_out", "tier_line.jsonl")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--graph-seed", type=int, default=2)
    ap.add_argument("--smax", type=int, default=1024)
    ap.add_argument("--degrees", type=int, nargs="+", default=[13, 6, 4],
                    help="neighbours a vertex draws: 13, 6 and 4 give "
                    "9.7e6, 2.2e6 and 1.0e6 multiplies at scale 14")
    ap.add_argument("--mode", default="bf16")
    args = ap.parse_args()

    import jax
    import numpy as np

    from chipbench import famgraph
    from combblas_tpu.parallel import spgemm as S
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat
    from combblas_tpu.semiring import PLUS_TIMES
    from combblas_tpu.utils import compile_cache

    if jax.default_backend() == "tpu":
        compile_cache.enable_compile_cache()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)

    def say(**kw):
        line = json.dumps(dict(
            device=jax.devices()[0].device_kind,
            library_line=S.WINDOWED_MAX_CELLS_PER_FLOP, **kw))
        print(line, flush=True)
        with open(OUT, "a") as f:
            f.write(line + "\n")

    for degree in args.degrees:
        n, rows, cols, vals, _ = famgraph.family_graph(
            args.scale, args.graph_seed, degree=degree, smax=args.smax)
        multiplies = int(np.dot(
            np.bincount(cols, minlength=n).astype(np.int64),
            np.bincount(rows, minlength=n).astype(np.int64)))
        A = SpParMat.from_global_coo(
            Grid.make(1, 1), rows, cols, vals, n, n)
        for tier in ("windowed", "scan"):
            secs = []
            try:
                for _ in range(4):
                    t0 = time.perf_counter()
                    C, digest = S.spgemm_job(
                        PLUS_TIMES, A, A, tier=tier, mode=args.mode)
                    secs.append(round(time.perf_counter() - t0, 4))
                    del C  # dropped before the next job, as the cell's is
            except Exception as e:  # a tier that does not fit says so
                say(degree=degree, tier=tier, error=repr(e)[:300])
                continue
            stats = jax.devices()[0].memory_stats() or {}
            say(degree=degree, n=n, nnz_in=len(rows), multiplies=multiplies,
                cells_per_multiply=round(n * n / multiplies, 1), tier=tier,
                rule=S.choose_tier_from_counts(
                    PLUS_TIMES, n, n * n, 1, multiplies, S.JOB_BACKEND,
                    k_dim=n, n_dim=n),
                nnz_out=digest["nnz"], cold_s=secs[0], warm_s=secs[1:],
                peak_gb=round(stats.get("peak_bytes_in_use", 0) / 1e9, 3))
    return 0


if __name__ == "__main__":
    sys.exit(main())
