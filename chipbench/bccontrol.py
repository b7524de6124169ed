"""The control of the BC cell's limits, through the cell's own checks and
on the host alone: what ``correct`` says of answers computed in another
precision than the configuration states.

    python3 -m chipbench.bccontrol --seed <n> --held-in bfloat16|float32

Builds the configuration's graph (``g500-s20-bc-1x1``: the same R-MAT
from the same seed, no device), draws the roots and the sample as
``drivers/serve_closed_bc.py`` does for ``--seed``, answers the sampled
requests with ``BCReference.dependencies_held_in`` (Brandes with
``sigma`` and ``delta`` rounded to the given type wherever they are
stored) and hands them to the driver's ``check_sample``.  The last line
of stdout is one JSON object: ``correct``, and how many problems name
each limit (``RTOL``: a score or a trial's sum against float64 Brandes;
``RTOL_SUM``: the sum rule).  bfloat16, the chip's precision below the
float32 the configuration states, has to come out NOT correct, and
float32 in numpy's row order (another order than the chip's) correct:
the exit code is 0 when it does and 1 when it does not.

Only the sampled requests are answered (the O(n) checks of every other
answer are not the precision's).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from . import bcref, graph
from .deploy import log
from .spec import CHECKOUT, Spec

CELL = "g500-s20bc.bc-sat"
#: requests the pretended run sent: what a 45 s window and its drain hold
REQUESTS = 160


def held_in(name: str):
    if name == "float32":
        return np.float32
    import ml_dtypes

    return getattr(ml_dtypes, name)


def control(spec: Spec, seed: int, dtype) -> dict:
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    drv = spec.load_module("drivers", mix["driver"])
    n, rows, cols, _ = graph.rmat_graph(
        int(cfg["scale"]), int(cfg["edgefactor"]), int(cfg["graph_seed"]))
    ref = bcref.BCReference(n, rows, cols)
    log(f"control: R-MAT scale {cfg['scale']}, n={n} nnz={len(rows)}")
    roots = graph.draw_roots(ref.bfs.deg, seed, 4096)
    trial = int(mix["trial"])
    sampler = drv.BCSampler(seed, trial, int(mix["check"]["exact"]),
                            int(mix["check"]["sum"]), ref.bfs.deg)
    for first in range(0, REQUESTS, trial):
        sampler.submitted(first)
    for idx in sorted(set(sampler.exact) | set(sampler.sum)):
        root = int(roots[idx % len(roots)])
        lv = ref.levels(root)
        sampler.take(idx, root, {
            "scores": ref.dependencies_held_in(root, dtype, lv),
            "batch_niter": int(lv.max()) + 1,
        })
    problems = drv.check_sample(ref, sampler)
    return {
        "correct": not problems,
        "checked": len(sampler.kept),
        "refused_by": {
            "RTOL": sum("reference says" in p for p in problems),
            "RTOL_SUM": sum("sum rule" in p for p in problems),
        },
        "problems": problems[:4],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--held-in", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--bench",
                    default=os.path.join(CHECKOUT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    out = control(Spec(args.bench), args.seed, held_in(args.held_in))
    out = dict(held_in=args.held_in, seed=args.seed, **out)
    print(json.dumps(out))
    return 0 if out["correct"] == (args.held_in == "float32") else 1


if __name__ == "__main__":
    sys.exit(main())
