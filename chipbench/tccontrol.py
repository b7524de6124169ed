"""The control of the TC cell's check, through the cell's own checks and
on the host alone: what ``correct`` says of three altered counts.

    python3 -m chipbench.tccontrol --seed <n> [--alter none|off_by_one|edge_removed|hi_dropped]

Builds the configuration's graph (``g500-s18-tc-1x1``: the same R-MAT
from the same seed, no device), takes the reference's own count for what
every job of a pretended run returned, alters it, and hands the jobs to
``drivers/library_count.py``'s ``check_jobs``:

- ``off_by_one``: the count plus one, the smallest fault an integer
  answer can hold;
- ``edge_removed``: the triple of the graph WITHOUT one edge drawn from
  ``--seed`` (its count recomputed by the reference on that graph, one
  edge fewer): what a dropped nonzero gives;
- ``hi_dropped``: the count a harvest gives that loses the ``hi`` half
  of its int32 (hi, lo) 15-bit split of 3 x triangles (what is left is
  ``(3 T mod 2^15) // 3``): the "next lower precision" of an exact
  integer count, as a sum kept in 15 bits.

The last line of stdout is one JSON object with ``correct``.  Every
alteration has to come out NOT correct and ``none`` correct: the exit
code is 0 when it does and 1 when it does not.  Without ``--alter`` all
four are tried, one line each.  Integers: the limit is equality, and no
tolerance stands where one would.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from . import graph, tcref
from .deploy import log
from .spec import CHECKOUT, Spec

CELL = "g500-s18tc.tc-batch"
#: jobs the pretended run held: what a 45 s window holds
JOBS = 8
ALTERATIONS = ("none", "off_by_one", "edge_removed", "hi_dropped")


def altered(ref: tcref.TCReference, rows, cols, seed: int, how: str,
            ) -> tuple[tuple[int, int, int], dict]:
    """The triple every job of the pretended run returns under ``how``,
    and what was done."""
    pairs = 2 * ref.edges  # what today's scan walks, before padding
    if how == "none":
        return (ref.triangles, pairs, ref.edges), {}
    if how == "off_by_one":
        return (ref.triangles + 1, pairs, ref.edges), {}
    if how == "hi_dropped":
        return ((3 * ref.triangles & 0x7FFF) // 3, pairs, ref.edges), {}
    rng = np.random.default_rng([seed, 0x7C])
    e = int(rng.integers(len(rows)))
    a, b = int(rows[e]), int(cols[e])
    gone = ((rows == a) & (cols == b)) | ((rows == b) & (cols == a))
    less = tcref.TCReference(ref.n, rows[~gone], cols[~gone])
    return (less.triangles, pairs, less.edges), {
        "removed": [a, b], "closed": ref.triangles - less.triangles}


def control(spec: Spec, seed: int, how: str, built=None) -> dict:
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    drv = spec.load_module("drivers", mix["driver"])
    picker = spec.load_module("drivers", "library_job").checked_jobs
    n, rows, cols, ref = built or build(cfg)
    triple, what = altered(ref, rows, cols, seed, how)
    picks = picker(seed, JOBS, int(mix["check"]["sampled"]))
    problems = drv.check_jobs(ref, [triple] * JOBS, picks)
    return dict({
        "correct": not problems,
        "checked": len(picks),
        "triple": list(triple),
        "problems": problems[:4],
    }, **what)


def build(cfg: dict):
    n, rows, cols, _ = graph.rmat_graph(
        int(cfg["scale"]), int(cfg["edgefactor"]), int(cfg["graph_seed"]))
    ref = tcref.TCReference(n, rows, cols)
    log(f"control: R-MAT scale {cfg['scale']}, n={n} nnz={len(rows)}, "
        f"{ref.edges} undirected edges, {ref.triangles} triangles")
    return n, rows, cols, ref


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--alter", choices=ALTERATIONS)
    ap.add_argument("--bench",
                    default=os.path.join(CHECKOUT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    spec = Spec(args.bench)
    built = build(spec.config(spec.cell(CELL)["config"]))
    ok = True
    for how in (args.alter,) if args.alter else ALTERATIONS:
        out = control(spec, args.seed, how, built)
        print(json.dumps(dict(alter=how, seed=args.seed, **out)), flush=True)
        ok &= out["correct"] == (how == "none")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
