"""The control of the MCL cell's limits, through the cell's own checks
and on the host alone: what ``correct`` says of the same clustering with
the inputs of every product held one precision down.

    python3 -m chipbench.mclcontrol --seed <n> [--inputs float64|float32|bfloat16]

Builds the configuration's graph (``hipmcl-fam-1x1``, no device), runs
``mclref.mcl_reference``'s own loop with both operands of every
expansion rounded ONCE to the given precision (the products accumulate
in float64, so this is the best a matrix unit fed such inputs can do),
and hands the pretended run's digest and the states after the checked
iterations to ``drivers/library_cluster.py``'s ``check_jobs``, against
the float64 reference:

- ``float64``: the reference held to itself;
- ``float32``: what the configuration states (float32 values; the
  program's ``bf16x3`` pass carries 2^-16 an operand, between this and
  the next);
- ``bfloat16``: ONE bfloat16 pass, which is what the chip's default
  precision and its ``f32`` and ``bf16`` modes are (2^-8 an operand).

The last line of stdout of each is one JSON object with ``correct`` and
the readings each limit is held against.  The first two have to come out
correct and ``bfloat16`` NOT: the exit code is 0 when they do.  Without
``--inputs`` all three are tried, one line each (minutes each at the
shipped scale: every one is a whole float64 clustering).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from . import mclref
from .deploy import log
from .spec import CHECKOUT, Spec

CELL = "hipmcl-fam.mcl-batch"
#: jobs the pretended run held
JOBS = 4
INPUTS = ("float64", "float32", "bfloat16")


def round_to(x: np.ndarray, how: str) -> np.ndarray:
    """float64 values rounded once to ``how``, as float64 (bfloat16:
    the nearest, ties to even, through float32)."""
    if how == "float64":
        return np.asarray(x, np.float64)
    x32 = np.asarray(x, np.float32)
    if how == "float32":
        return x32.astype(np.float64)
    assert how == "bfloat16", how
    bits = x32.view(np.uint32).astype(np.uint64)
    bits = (bits + np.uint64(0x7FFF) + ((bits >> np.uint64(16))
                                        & np.uint64(1))) >> np.uint64(16)
    return (bits << np.uint64(16)).astype(np.uint32).view(
        np.float32).astype(np.float64)


def control(spec: Spec, seed: int, how: str, built) -> dict:
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    drv = spec.load_module("drivers", mix["driver"])
    n, rows, cols, vals, ref = built
    pool = ref["pool"]

    def operand(A):
        out = A.copy()
        out.data = round_to(A.data, how)
        return out

    run = mclref.mcl_reference(
        n, rows, cols, vals, **{k: cfg["mcl"][k] for k in mclref.PARAMS},
        keep=sorted(ref["matrices"]), columns=pool,
        operand=None if how == "float64" else operand)
    digest = {
        "iters": run["iters"], "chaos": np.asarray(run["chaos"], np.float32),
        "stored": np.asarray(run["stored"]), "clusters": run["clusters"],
        "fingerprint": mclref.fingerprint(run["labels"]),
        # the rule's line, for the iterations the driver would check
        "tiers": tuple(
            "windowed" if 16 * c["products"] >= n * n else "scan"
            for c in run["counts"]),
    }
    states = {}
    for it in drv.checked_iterations(digest["tiers"], run["matrices"]):
        coo = run["matrices"][it].tocoo()
        states[it] = (coo.row, pool[coo.col], coo.data)
    columns = drv.sample_columns(seed, mix, pool)
    problems = drv.check_jobs(
        ref, [digest] * JOBS, {"digest": digest, "states": states},
        cfg["limits"], n, columns)
    k = min(run["iters"], ref["iters"])
    got, exp = np.asarray(run["chaos"][:k]), np.asarray(ref["chaos"][:k])
    sg, se = (np.asarray(r["stored"][:k], np.float64) for r in (run, ref))
    dist = {
        it: mclref.check_matrix(
            n, st, ref["matrices"][it][:, columns], pool[columns],
            cfg["limits"])[1:]
        for it, st in states.items()}
    return {
        "correct": not problems,
        "iters": run["iters"], "clusters": run["clusters"],
        "same_labels": bool(np.array_equal(run["labels"], ref["labels"])),
        "chaos_rel": float(np.max(np.abs(got - exp) / np.maximum(
            exp, 1e-30))),
        "chaos_abs": float(np.max(np.abs(got - exp))),
        "chaos_over_limit": float(np.max(np.abs(got - exp) / (
            cfg["limits"]["chaos_rel"] * exp + cfg["limits"]["chaos_abs"]))),
        "stored_rel": float(np.max(np.abs(sg - se) / se)),
        "column_l1_max": {it: d[0] for it, d in dist.items()},
        "column_l1_mean": {it: d[1] for it, d in dist.items()},
        "problems": [p[:200] for p in problems[:3]],
    }


def build(spec: Spec):
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    drv = spec.load_module("drivers", mix["driver"])
    cache = os.path.join(
        spec.cache_dir(), f"{cfg['name']}-{drv.cache_key(cfg)}")
    n, rows, cols, vals, how = drv.load_graph(cfg, cache)
    ref = drv.load_reference(cfg, mix, cache, n, rows, cols, vals)
    log(f"control: family graph scale {cfg['scale']} ({how}), n={n}, "
        f"{len(rows) // 2} undirected edges; the reference ({ref['how']}): "
        f"{ref['iters']} iterations, {ref['clusters']} clusters")
    return n, rows, cols, vals, ref


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--inputs", choices=INPUTS)
    ap.add_argument("--bench",
                    default=os.path.join(CHECKOUT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    spec = Spec(args.bench)
    built = build(spec)
    ok = True
    for how in (args.inputs,) if args.inputs else INPUTS:
        out = control(spec, args.seed, how, built)
        print(json.dumps(dict(inputs=how, seed=args.seed, **out)),
              flush=True)
        ok &= out["correct"] == (how != "bfloat16")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
