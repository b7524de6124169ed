#!/usr/bin/env python
"""Every MCL loop the library can name on one chip, ONE warm job each on
the MCL cell's graph (``chipbench/famgraph.py`` at the configuration's
scale and seed, HipMCL's published select 1100 / recover 1400 / prune
1e-4 / inflation 2): seconds, iterations, clusters and the allocator's
peak, or how far it got inside its time.  ROADMAP D21's table.

    chiprun --timeout 3000 -- python scripts/mcl_loops.py
    JAX_PLATFORMS=cpu python scripts/mcl_loops.py --scale 9 --degree 24 --smax 96 \\
        --select 40 --recover 60 --loops job dense

A loop is a child process (a chip belongs to one process at a time; the
parent touches no JAX) that runs its job twice, the first one compiling,
and is killed at ``--limit`` seconds: the line then says what it had
finished.  ``job`` (``mcl_job``) also times every iteration of its warm
job.  ``mcl(layers=...)``'s 3D loop needs a mesh and is not here.  One
JSON line a loop on stdout and in ``chiprun_out/mcl_loops.jsonl``.
``--loops job --cells-per-flop 16 64 256 512`` is the ladder that set
``parallel/spgemm.py:WINDOWED_MAX_CELLS_PER_FLOP`` (PR 46): ``mcl_job``
with the rule's line moved for that child alone, one line a value with
the job's ``tiers``, ``iter_s`` and ``seconds``; 0 keeps the library's
value and prints it.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import re
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join("chiprun_out", "mcl_loops.jsonl")

#: name -> keyword arguments of ``models.mcl.mcl`` (None: ``mcl_job``)
LOOPS = {
    "job": None,
    "dense": dict(expansion="dense"),
    "sparse": dict(),
    "scan": dict(scan=True),
    "block4": dict(scan=True, chaos_every=4),
}


def child(args) -> int:
    import jax
    import numpy as np

    from chipbench import famgraph
    from combblas_tpu.models import mcl as M
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat
    from combblas_tpu.utils import compile_cache

    if jax.default_backend() == "tpu":
        compile_cache.enable_compile_cache()
    from combblas_tpu.parallel import spgemm

    if args.cells_per_flop[0]:
        spgemm.WINDOWED_MAX_CELLS_PER_FLOP = args.cells_per_flop[0]

    def say(**kw):
        print(json.dumps(dict(
            loop=args.child,
            cells_per_flop=spgemm.WINDOWED_MAX_CELLS_PER_FLOP, **kw)),
            flush=True)

    n, rows, cols, vals, _ = famgraph.family_graph(
        args.scale, args.graph_seed, degree=args.degree, smax=args.smax)
    A = SpParMat.from_global_coo(Grid.make(1, 1), rows, cols, vals, n, n)
    say(stage="graph", n=n, edges=len(rows) // 2,
        device=jax.devices()[0].device_kind)
    kw = LOOPS[args.child]
    marks = []

    def run(hook=None):
        if kw is None:
            labels, d = M.mcl_job(
                A, select=args.select, recover=args.recover, hook=hook)
            return labels, d["iters"], float(d["chaos"][-1]), d
        labels, it, ch = M.mcl(
            A, 2.0, select_num=args.select, recover_num=args.recover,
            max_iters=64, **kw)
        return labels, it, ch, None

    # the job's second warm run says how far one reading can be trusted
    for stage in ("cold", "warm") + ("warm",) * (kw is None):
        marks.clear()
        hook = None
        if kw is None and stage == "warm":
            hook = lambda it, tier, fetch: marks.append(  # noqa: E731
                time.perf_counter())
        t0 = time.perf_counter()
        labels, it, ch, d = run(hook)
        lab = np.asarray(labels.blocks).reshape(-1)[:n]
        secs = time.perf_counter() - t0
        stats = jax.devices()[0].memory_stats() or {}
        extra = {}
        if d is not None:
            extra = dict(tiers=list(d["tiers"]),
                         stored=[int(x) for x in d["stored"]])
            if marks:
                extra["iter_s"] = [
                    round(b - a, 4) for a, b in zip([t0] + marks, marks)]
        say(stage=stage, seconds=round(secs, 3), iters=int(it),
            chaos=float(ch), clusters=int((lab == np.arange(n)).sum()),
            peak_gb=round(stats.get("peak_bytes_in_use", 0) / 1e9, 3),
            reserved_gb=round(stats.get("peak_bytes_reserved", 0) / 1e9, 3),
            **extra)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--graph-seed", type=int, default=2)
    ap.add_argument("--degree", type=int, default=128)
    ap.add_argument("--smax", type=int, default=1024)
    ap.add_argument("--select", type=int, default=1100)
    ap.add_argument("--recover", type=int, default=1400)
    ap.add_argument("--limit", type=float, default=360.0)
    ap.add_argument("--cells-per-flop", type=float, nargs="+", default=[0.0],
                    help="the rule's line (WINDOWED_MAX_CELLS_PER_FLOP), "
                    "every loop once a value, for that child alone: where "
                    "mcl_job leaves the dense tier; 0 keeps the library's")
    ap.add_argument("--loops", nargs="*", default=list(LOOPS))
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child(args)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    for line, loop in itertools.product(args.cells_per_flop, args.loops):
        cmd = [sys.executable, os.path.abspath(__file__), "--child", loop,
               "--cells-per-flop", str(line)] + [
            a for k in ("scale", "graph_seed", "degree", "smax", "select",
                        "recover")
            for a in (f"--{k.replace('_', '-')}", str(getattr(args, k)))]
        t0 = time.perf_counter()
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.limit, cwd=ROOT)
            out, how = p.stdout, f"exit {p.returncode}"
            if p.returncode:
                # the exception's own line, not the traceback filter's
                # note after it; the whole tail beside the table
                err = p.stderr.strip().splitlines()
                named = [ln for ln in err if re.match(
                    r"^[\w.]*(Error|Exception|Exit)\b", ln)]
                how += ": " + (named or err or [""])[-1][:400]
                with open(OUT.replace(".jsonl", f".{loop}.err"), "w") as f:
                    f.write("\n".join(err[-200:]) + "\n")
        except subprocess.TimeoutExpired as e:
            out = (e.stdout or b"").decode() if isinstance(
                e.stdout, bytes) else (e.stdout or "")
            how = f"killed at {args.limit:g} s"
        lines = [ln for ln in out.splitlines() if ln.startswith("{")]
        lines.append(json.dumps(dict(
            loop=loop, cells_per_flop=line, stage="end", how=how,
            wall=round(time.perf_counter() - t0, 1))))
        with open(OUT, "a") as f:
            for ln in lines:
                print(ln, flush=True)
                f.write(ln + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
