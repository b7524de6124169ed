"""The control of the product cell's check, through the cell's own checks
and on the host alone: what ``correct`` says of the same product with
its accumulator held in bfloat16.

    python3 -m chipbench.sqcontrol --seed <n> [--held-in float32|bfloat16|bfloat16_stalled]

Builds the configuration's graph (``g500-sq-1x1``: the same R-MAT from
the same seed, no device), takes the reference's own ``C = A @ A`` for
what every job of a pretended run produced, holds its values in the
given precision, and hands the jobs' digests and the last job's stored
entries to ``drivers/library_product.py``'s ``check_jobs``:

- ``float32``: what the configuration states (f32 accumulation): every
  entry is an integer below 2^24, held exactly;
- ``bfloat16``: every exact sum rounded ONCE to bfloat16 (8 significant
  bits, ties to even): the best any bfloat16 accumulator can do,
  whatever order it adds in.  Entries up to 256 survive; above, only
  multiples of 2, 4, 8 ... do;
- ``bfloat16_stalled``: ones added one at a time into a bfloat16
  accumulator, which stops at 256 (256 + 1 rounds back to 256): every
  entry above 256 reads 256.

The last line of stdout of each is one JSON object with ``correct`` and
``differing_entries``.  ``float32`` has to come out correct and both
others NOT: the exit code is 0 when they do and 1 when they do not.
Without ``--held-in`` all three are tried, one line each.  Integers: the
limit is equality, and no tolerance stands where one would.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from . import graph, sqref
from .deploy import log
from .spec import CHECKOUT, Spec

CELL = "g500-sq.spgemm-batch"
#: jobs the pretended run held: what a 45 s window holds
JOBS = 6
HELD_IN = ("float32", "bfloat16", "bfloat16_stalled")


def round_to_bfloat16(x: np.ndarray) -> np.ndarray:
    """Non-negative integers below 2^24 rounded to the nearest bfloat16,
    ties to even, as int64."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + np.uint64(0x7FFF) + ((bits >> np.uint64(16))
                                        & np.uint64(1))) >> np.uint64(16)
    return (bits << np.uint64(16)).astype(np.uint32).view(
        np.float32).astype(np.int64)


def held(data: np.ndarray, how: str) -> np.ndarray:
    if how == "float32":
        return np.asarray(data, np.float32).astype(np.int64)
    if how == "bfloat16":
        return round_to_bfloat16(data)
    assert how == "bfloat16_stalled", how
    return np.minimum(data, 256)


def control(spec: Spec, seed: int, how: str, built=None) -> dict:
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    drv = spec.load_module("drivers", mix["driver"])
    picker = spec.load_module("drivers", "library_job").checked_jobs
    ref = built or build(cfg)
    c = ref.C.copy()
    c.data = held(ref.C.data, how)
    digest = sqref.digest_of(c)
    coo = c.tocoo()
    picks = picker(seed, JOBS, int(mix["check"]["sampled"]))
    problems = drv.check_jobs(
        ref, [digest] * JOBS, picks, (coo.row, coo.col, coo.data))
    return {
        "correct": not problems,
        "checked": len(picks),
        "entries": int(ref.C.nnz),
        "differing_entries": int((c.data != ref.C.data).sum()),
        "largest": ref.largest,
        # a digest's and the last job's C's
        "problems": [p[:200] for p in problems[:1] + problems[1:][-1:]],
    }


def build(cfg: dict) -> sqref.SQReference:
    n, rows, cols, _ = graph.rmat_graph(
        int(cfg["scale"]), int(cfg["edgefactor"]), int(cfg["graph_seed"]))
    ref = sqref.SQReference(n, rows, cols)
    log(f"control: R-MAT scale {cfg['scale']}, n={n} nnz={len(rows)}, "
        f"C = A @ A has {ref.C.nnz} entries from {ref.products} "
        f"products, the largest {ref.largest}")
    return ref


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--held-in", choices=HELD_IN)
    ap.add_argument("--bench",
                    default=os.path.join(CHECKOUT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    spec = Spec(args.bench)
    built = build(spec.config(spec.cell(CELL)["config"]))
    ok = True
    for how in (args.held_in,) if args.held_in else HELD_IN:
        out = control(spec, args.seed, how, built)
        print(json.dumps(dict(held_in=how, seed=args.seed, **out)),
              flush=True)
        ok &= out["correct"] == (how == "float32")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
