"""The control of the matching cell's check, through the cell's own
checks: what ``correct`` says of the timed path's own answer with one
fault put into it.

    python3 -m chipbench.mcmcontrol --seed <n> --fault undone|stray|none

Brings the configuration up as the cell does (``g500-mcm-1x1``: the same
pattern from the same seed, on the device), runs the cell's entry once,
and puts one fault, drawn from ``--seed``, into the answer:

``undone``  one augmentation undone: a matched pair is unmatched.  What
            is left is still a matching of the pattern, one pair short
            of the maximum: only the cardinality's limit can tell.
``stray``   a matched pair that is no edge: two matched pairs swap
            their columns where that makes a pair the pattern does not
            store.  The mates stay each other's inverse and as many as
            the maximum: only the edge lookup can tell.
``none``    the answer as it came.

The faulted answer stands for every job of a pretended run and goes to
``drivers/library_match.py``'s ``check_jobs``.  The last line of stdout
is one JSON object with ``correct``.  Either fault has to come out NOT
correct and ``none`` correct: the exit code is 0 when it does and 1 when
it does not.  There is no precision below the configuration's to try:
mates are integers and every limit is equality.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from . import deploy, mcmdeploy, mcmref
from .deploy import log
from .spec import CHECKOUT, Spec, resolve

CELL = "g500-mcm.mcm-batch"
#: jobs the pretended run held
JOBS = 8


def undo_one(mate_row, mate_col, rng):
    """One matched pair unmatched: ``(mate_row, mate_col, what)``."""
    mr, mc = mate_row.copy(), mate_col.copy()
    r = int(rng.choice(np.flatnonzero(mr >= 0)))
    c = int(mr[r])
    mr[r], mc[c] = -1, -1
    return mr, mc, [r, c]


def stray_pair(ref: mcmref.McmReference, mate_row, mate_col, rng):
    """Two matched pairs with their columns swapped, where a swapped
    pair is no stored nonzero: ``(mate_row, mate_col, what)``."""
    mr, mc = mate_row.copy(), mate_col.copy()
    matched = np.flatnonzero(mr >= 0)
    for _ in range(1000):
        r1, r2 = (int(r) for r in rng.choice(matched, 2, replace=False))
        c1, c2 = int(mr[r1]), int(mr[r2])
        if not ref.is_edge([r1], [c2])[0]:
            mr[r1], mc[c2], mr[r2], mc[c1] = c2, r1, c1, r2
            return mr, mc, [r1, c2]
    raise SystemExit("mcmcontrol: no swap makes a pair that is no edge")


def control(spec: Spec, seed: int, fault: str) -> dict:
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    drv = spec.load_module("drivers", mix["driver"])
    deploy.start_backend(int(cell["chips"]))
    dep = mcmdeploy.deploy_bipartite(cfg)
    out = resolve(mix["entry"])(dep.M)
    ref = mcmref.McmReference(dep.nr, dep.nc, dep.rows, dep.cols)
    log(f"control: {dep.nr} x {dep.nc}, nnz={len(dep.rows)}, the job's "
        f"cardinality {out.cardinality}, the maximum {ref.cardinality}")
    rng = np.random.default_rng([seed, 0x3C3C])
    mr, mc, what = out.mate_row, out.mate_col, None
    if fault == "undone":
        mr, mc, what = undo_one(mr, mc, rng)
    elif fault == "stray":
        mr, mc, what = stray_pair(ref, mr, mc, rng)
    answer = (mr, mc, int((mc >= 0).sum()), int(out.phases))
    picks = drv.checked_jobs(seed, JOBS, int(mix["check"]["sampled"]))
    problems = drv.check_jobs(ref, [answer] * JOBS, picks)
    return {
        "correct": not problems,
        "checked": len(picks),
        "pair": what,
        "problems": problems[:4],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault", default="undone",
                    choices=("undone", "stray", "none"))
    ap.add_argument("--bench",
                    default=os.path.join(CHECKOUT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    out = control(Spec(args.bench), args.seed, args.fault)
    out = dict(fault=args.fault, seed=args.seed, **out)
    print(json.dumps(out))
    return 0 if out["correct"] == (args.fault == "none") else 1


if __name__ == "__main__":
    sys.exit(main())
