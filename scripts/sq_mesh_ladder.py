#!/usr/bin/env python
"""The stage schedules of a product job on the mesh, on the four chips:
``spgemm_job(PLUS_TIMES, A, A, tier="windowed", backend="dot",
mode="bf16")`` on the mesh cell's matrix (Graph500 R-MAT scale 15 on
``Grid.make(2, 2)``, ``chipbench/configs/g500-sq15-2x2.json``) under the
GATHERED schedule, the CAROUSEL and the carousel with ``pipeline=False``
(the serial control): one job that compiles and three warm ones each,
seconds a job and peak memory a chip.  ``spgemm_job`` runs ONE schedule
(``run_windowed``'s defaults, the gathered one) and takes no argument
for it, so a rung runs the job's own steps with the schedule given to
``run_windowed``: the symbolic pass (``summa_stage_flops``,
``plan_windowed``), ``run_windowed``, ``_packed``, ``spgemm_digest`` read
by the host.  ROADMAP D4 and ``PERF.md`` section 6 have the table the
chip printed (PR 48, through the job entry with its schedule patched, a
form the review took out; the steps are the same).

    chiprun --chips 4 -- python scripts/sq_mesh_ladder.py
    JAX_PLATFORMS=cpu python scripts/sq_mesh_ladder.py --scale 9

A child process a rung (a chip's peak memory is the process's, and a
chip belongs to one process at a time: this parent never touches JAX);
one JSON line a rung on stdout and in ``chiprun_out/sq_mesh_ladder.jsonl``.
Every rung's digest must be the first's.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join("chiprun_out", "sq_mesh_ladder.jsonl")
RUNGS = {
    "gathered": dict(ring=False, pipeline=True),
    "carousel": dict(ring=True, pipeline=True),
    "carousel-serial": dict(ring=True, pipeline=False),
}
JOB = dict(backend="dot", mode="bf16")  # the cell's, under tier windowed


def rung(name: str, scale: int, jobs: int) -> dict:
    """One schedule, in this process."""
    import jax
    import numpy as np

    from chipbench import graph
    from combblas_tpu.ops.spgemm import combine_hilo
    from combblas_tpu.parallel import spgemm as S
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat
    from combblas_tpu.semiring import PLUS_TIMES
    from combblas_tpu.utils import compile_cache

    if jax.default_backend() == "tpu":
        compile_cache.enable_compile_cache()
    n, rows, cols, _ = graph.rmat_graph(scale, 16, 1)
    A = SpParMat.from_global_coo(
        Grid.make(2, 2), rows, cols, np.ones(len(rows), np.float32), n, n)
    secs, digest = [], None
    for _ in range(1 + jobs):
        t0 = time.perf_counter()
        S.host_value(S.summa_stage_flops(A, A, padded=False))
        plan = S.plan_windowed(PLUS_TIMES, A, A, backend=JOB["backend"])
        C = S._packed(
            S.run_windowed(
                PLUS_TIMES, A, A, plan, mode=JOB["mode"], **RUNGS[name]),
            plan.chunk_caps())
        nnz, hilo, _, _, prints = jax.device_get(S.spgemm_digest(C))
        digest = dict(nnz=int(nnz), sum=combine_hilo(hilo), prints=prints)
        secs.append(round(time.perf_counter() - t0, 4))
        capacity, tiles = int(C.capacity), np.asarray(C.nnz).ravel().tolist()
        del C  # dropped before the next job, as the cell's is
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return dict(
        schedule=name, **RUNGS[name], scale=scale,
        device=jax.devices()[0].device_kind, devices=len(jax.devices()),
        cold_s=secs[0], warm_s=secs[1:],
        nnz=digest["nnz"], sum=digest["sum"],
        prints_xor=int(np.bitwise_xor.reduce(digest["prints"])),
        pack_capacity=capacity, tile_nnz=tiles,
        peak_in_use_gb=[
            round(s.get("peak_bytes_in_use", 0) / 1e9, 3) for s in stats],
        peak_reserved_gb=[
            round(s.get("peak_bytes_reserved", 0) / 1e9, 3) for s in stats],
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=15)
    ap.add_argument("--jobs", type=int, default=3, help="warm jobs a rung")
    ap.add_argument("--rungs", nargs="+", choices=list(RUNGS),
                    default=list(RUNGS))
    ap.add_argument("--rung", choices=list(RUNGS),
                    help="run this one rung here (what a child is given)")
    args = ap.parse_args()
    if args.rung:
        print(json.dumps(rung(args.rung, args.scale, args.jobs)), flush=True)
        return 0
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    env = dict(os.environ)
    if env.get("JAX_PLATFORMS", "").lower() == "cpu":
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    found = []
    for name in args.rungs:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--rung", name,
             "--scale", str(args.scale), "--jobs", str(args.jobs)],
            cwd=ROOT, env=env, capture_output=True, text=True)
        lines = r.stdout.strip().splitlines()
        if r.returncode or not lines:
            out = dict(schedule=name, failed=r.returncode,
                       stderr=r.stderr[-1500:])
        else:
            out = json.loads(lines[-1])
            found.append(out)
        print(json.dumps(out), flush=True)
        with open(OUT, "a") as f:
            f.write(json.dumps(out) + "\n")
    same = all(
        (o["nnz"], o["sum"], o["prints_xor"]) == (
            found[0]["nnz"], found[0]["sum"], found[0]["prints_xor"])
        for o in found)
    return 0 if same and len(found) == len(args.rungs) else 1


if __name__ == "__main__":
    sys.exit(main())
