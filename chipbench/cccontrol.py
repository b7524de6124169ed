"""The control of the CC cell's check, through the cell's own checks and
on the host alone: what ``correct`` says of labels that put ONE edge's
two ends into different components.

    python3 -m chipbench.cccontrol --seed <n> [--edges 0|1]

Builds the configuration's graph (``g500-s20-cc-1x1``: the same R-MAT
from the same seed, no device), takes the reference's own labels for
what every job of a pretended run returned, relabels apart the two ends
of ``--edges`` edges drawn from ``--seed`` (the end that is not its
component's smallest id labels itself: a split of one vertex, the
smallest fault an integer answer can hold), and hands the jobs to
``drivers/library_job.py``'s ``check_jobs``.  The last line of stdout is
one JSON object with ``correct``.  One edge has to come out NOT correct
and none correct: the exit code is 0 when it does and 1 when it does
not.  There is no precision below the configuration's to try: labels are
integers and the limit is equality.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from . import ccref, graph
from .deploy import log
from .spec import CHECKOUT, Spec

CELL = "g500-s20cc.cc-batch"
#: jobs the pretended run held: what a 45 s window holds
JOBS = 16


def control(spec: Spec, seed: int, edges: int) -> dict:
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    drv = spec.load_module("drivers", mix["driver"])
    n, rows, cols, _ = graph.rmat_graph(
        int(cfg["scale"]), int(cfg["edgefactor"]), int(cfg["graph_seed"]))
    ref = ccref.CCReference(n, rows, cols)
    log(f"control: R-MAT scale {cfg['scale']}, n={n} nnz={len(rows)}, "
        f"{ref.components} components")
    labels = ref.labels.copy()
    rng = np.random.default_rng([seed, 0xCC])
    apart = []
    for e in rng.choice(len(rows), edges, replace=False):
        a, b = int(rows[e]), int(cols[e])
        v = a if labels[a] != a else b  # not the component's smallest
        labels[v] = v
        apart.append([a, b])
    picks = drv.checked_jobs(seed, JOBS, int(mix["check"]["sampled"]))
    problems = drv.check_jobs(ref, [(labels, 5, 1)] * JOBS, picks)
    return {
        "correct": not problems,
        "checked": len(picks),
        "apart": apart,
        "problems": problems[:4],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--edges", type=int, default=1, choices=(0, 1))
    ap.add_argument("--bench",
                    default=os.path.join(CHECKOUT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    out = control(Spec(args.bench), args.seed, args.edges)
    out = dict(edges=args.edges, seed=args.seed, **out)
    print(json.dumps(out))
    return 0 if out["correct"] == (args.edges == 0) else 1


if __name__ == "__main__":
    sys.exit(main())
