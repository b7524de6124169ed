"""The control of the MESH product cell's check, through the cell's own
checks and on the host alone: what ``correct`` says of the same product
with its accumulator held in bfloat16.  ``sqcontrol.py``'s control (its
precisions, its rounding, its pretended run) for the cell whose last C
comes back a tile at a time:

    python3 -m chipbench.sqmcontrol --seed <n> [--held-in float32|bfloat16|bfloat16_stalled] [--on-mesh]

Builds the configuration's graph (``g500-sq15-2x2``: the same R-MAT from
the same seed, no device), takes the reference's own ``C = A @ A`` for
what every job produced, holds its values in the given precision, cuts
it into the grid's tiles as the chips would hold them, and hands the
jobs' digests to ``drivers/library_product.py``'s ``check_jobs`` and
the tiles to ``drivers/library_product_mesh.py``'s ``check_tiles``.
One JSON line each (``correct``, ``differing_entries``, the tiles that
failed); ``float32`` has to come out correct and both others NOT: the
exit code is 0 when they do.

``--on-mesh`` runs the control ON THE DEVICES instead: ONE real job of
the cell's entry on the configuration's grid, with every stage
product and every window's accumulator held in the given precision
(``bfloat16``: the matrix unit's f32 sums rounded to bfloat16 as they
leave it, a stage at a time, and the stages' sum rounded again, as a
bfloat16 add rounds it, before the window is extracted), its digest
handed to
``check_jobs`` and its tiles, read back from every device, to
``check_tiles``.  ``bfloat16_stalled`` has no program and is refused.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

import numpy as np

from . import sqcontrol, sqref
from .spec import CHECKOUT, Spec

CELL = "g500-sq15x4.spgemm-mesh"


def tiles_of(c, grid: tuple[int, int]) -> list[tuple]:
    """A canonical CSR matrix as the grid's tiles would store it:
    ``(i, j, rows, cols, vals)`` a tile, in GLOBAL coordinates."""
    n = c.shape[0]
    lr, lc = -(-n // grid[0]), -(-n // grid[1])
    coo = c.tocoo()
    owner = (coo.row // lr) * grid[1] + coo.col // lc
    out = []
    for i in range(grid[0]):
        for j in range(grid[1]):
            keep = owner == i * grid[1] + j
            out.append((i, j, coo.row[keep], coo.col[keep], coo.data[keep]))
    return out


def control(spec: Spec, seed: int, how: str, built=None) -> dict:
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    mesh = spec.load_module("drivers", mix["driver"])
    product = spec.load_module("drivers", "library_product")
    picker = spec.load_module("drivers", "library_job").checked_jobs
    ref = built or sqcontrol.build(cfg)
    c = ref.C.copy()
    c.data = sqcontrol.held(ref.C.data, how)
    digest = sqref.digest_of(c)
    grid = tuple(cfg["grid"])
    picks = picker(seed, sqcontrol.JOBS, int(mix["check"]["sampled"]))
    problems = product.check_jobs(
        ref, [digest] * sqcontrol.JOBS, picks, None)
    by_tile = mesh.check_tiles(ref, tiles_of(c, grid), grid)
    return {
        "correct": not problems and not by_tile,
        "checked": len(picks),
        "entries": int(ref.C.nnz),
        "differing_entries": int((c.data != ref.C.data).sum()),
        "largest": ref.largest,
        "tiles_refused": len(by_tile),
        # a digest's and a tile's
        "problems": [p[:200] for p in problems[:1] + by_tile[:1]],
    }


def on_mesh(spec: Spec, how: str, built=None) -> dict:
    """The control run by the devices: one job of the mix's entry on the
    configuration's grid, every stage product's result and every
    window's accumulator held in ``how``, through the cell's own
    checks."""
    import jax
    import jax.numpy as jnp

    from combblas_tpu.parallel import spgemm as S
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat

    from . import graph
    from .spec import resolve

    assert how in ("float32", "bfloat16"), how
    cell = spec.cell(CELL)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    mesh = spec.load_module("drivers", mix["driver"])
    product = spec.load_module("drivers", "library_product")
    ref = built or sqcontrol.build(cfg)
    n, rows, cols, _ = graph.rmat_graph(
        int(cfg["scale"]), int(cfg["edgefactor"]), int(cfg["graph_seed"]))
    grid = tuple(cfg["grid"])
    A = SpParMat.from_global_coo(
        Grid.make(*grid), rows, cols, np.ones(len(rows), np.float32), n, n)
    exact = S._mxu_dot, S._extract_window_2d

    def held_dot(da, db, mode, out_dtype):
        assert mode == "bf16", mode  # the mix's
        return jnp.dot(
            da.astype(jnp.bfloat16), db.astype(jnp.bfloat16),
            preferred_element_type=jnp.bfloat16).astype(out_dtype)

    def held_extract(acc, *args, **kw):
        # two bfloat16 stage products added exactly, then rounded: what
        # a bfloat16 add gives.  ``reduce_precision``, not a cast there
        # and back: the chip's compiler keeps that round trip in
        # float32 inside a fusion (``parallel/spgemm.py:_split_bf16``)
        return exact[1](
            jax.lax.reduce_precision(acc, exponent_bits=8, mantissa_bits=7),
            *args, **kw)

    if how == "bfloat16":
        S._mxu_dot, S._extract_window_2d = held_dot, held_extract
        jax.clear_caches()  # a program traced with the exact product
    try:
        C, digest = resolve(mix["entry"])(
            resolve(mix["semiring"]), A, A, **mix["job"])
    finally:
        if how == "bfloat16":
            S._mxu_dot, S._extract_window_2d = exact
            jax.clear_caches()
    tiles = mesh.stored_tiles(C)
    del C
    problems = product.check_jobs(ref, [digest], [0], None)
    by_tile = mesh.check_tiles(ref, tiles, grid)
    counts = [re.search(r"(\d+) of \d+ entries hold another value", p)
              for p in by_tile]
    return {
        "correct": not problems and not by_tile,
        "entries": int(ref.C.nnz),
        "stored": [len(t[2]) for t in tiles],
        # None where a tile differs in its coordinates, not in values
        "differing_entries": (
            sum(int(m.group(1)) for m in counts) if all(counts) else None),
        "largest": ref.largest,
        "tiles_refused": len(by_tile),
        "problems": [p[:200] for p in problems[:1] + by_tile],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--held-in", choices=sqcontrol.HELD_IN)
    ap.add_argument("--on-mesh", action="store_true",
                    help="one real job on the configuration's grid")
    ap.add_argument("--bench",
                    default=os.path.join(CHECKOUT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    spec = Spec(args.bench)
    built = sqcontrol.build(spec.config(spec.cell(CELL)["config"]))
    ok = True
    for how in (args.held_in,) if args.held_in else sqcontrol.HELD_IN:
        if args.on_mesh:
            if how == "bfloat16_stalled":
                continue
            out = dict(on_mesh=True, **on_mesh(spec, how, built))
        else:
            out = control(spec, args.seed, how, built)
        print(json.dumps(dict(held_in=how, seed=args.seed, **out)),
              flush=True)
        ok &= out["correct"] == (how == "float32")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
