#!/usr/bin/env python
"""What a step of the triangle count's harvest costs by how it fetches,
at the cell's shapes: ``g500-s18-tc-1x1``'s graph (n = 2^18), its kept
pairs front-packed as ``models/tc.py`` packs them, then for each table
shape the pack's ms and the harvest's by route:

- ``rows`` (``uint32[n, nw]``, the ``jnp`` loop): two row gathers and a
  streaming popcount a step of ``T`` pairs, ``T`` from ``--steps``
  (8,192 is the loop before PR 47; at 2,048 and under the compiler
  keeps both gathered blocks in its fast memory);
- ``row_tiles`` (``uint32[n, nw / 128, 128]``, the fused kernel
  ``pallas_kernels.pair_popcount_partials`` on a TPU): ``K`` pairs' row
  copies in flight, ``K`` from ``--groups``;
- ``row_tiles_3idx``: the pack alone, the same table scattered under
  three indices (``pack_support_bits``'s scatter-add writes it as ``[n
  * nw / 128, 128]`` under two and reshapes for nothing; since PR 49 a
  TPU packs ``row_tiles`` on the chip, no scatter: the pack's own
  ladder is ``scripts/tc_pack_ladder.py``).

    chiprun -- python scripts/tc_harvest_ladder.py
    JAX_PLATFORMS=cpu python scripts/tc_harvest_ladder.py --scale 15 --steps 8192 256

Each time is the best and the median of ``--repeats`` runs after one
that compiles; ``gbps`` is what the walked pairs' rows weigh (pairs x 2
x n/8 B) over the best.  One JSON line a rung on stdout and in
``chiprun_out/tc_harvest_ladder.jsonl`` (with the device it ran on: a
CPU's times say nothing about the chip, and a CPU runs the ``jnp`` loop
on both tables).  Every rung's count is held to the first's; a rung that
differs exits 1.  Re-run before moving ``ops/spgemm.py:HARVEST_GROUP``
or the table's shape.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from chipbench import graph
from combblas_tpu.ops import spgemm as ops
from combblas_tpu.utils import compile_cache

OUT = os.path.join("chiprun_out", "tc_harvest_ladder.jsonl")
CHUNK = 8192  # the pair list's padding: models/tc.py:HARVEST_CHUNK


def timed(fn, *args, repeats: int):
    """(result, best s, median s, first s) of ``fn(*args)``."""
    walls = []
    for _ in range(repeats + 1):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        walls.append(time.perf_counter() - t0)
    return out, min(walls[1:]), statistics.median(walls[1:]), walls[0]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=18)
    ap.add_argument("--edgefactor", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--steps", type=int, nargs="*",
                    default=[8192, 2048, 1024, 512, 256],
                    help="T: pairs a step of the jnp loop (T | 8192)")
    ap.add_argument("--groups", type=int, nargs="*", default=[16, 32, 64],
                    help="K: pairs a group of the fused kernel (8 | K)")
    ap.add_argument("--tables", nargs="*",
                    default=["rows", "row_tiles_3idx", "row_tiles"])
    args = ap.parse_args()
    compile_cache.enable_compile_cache()
    dev = jax.devices()[0]
    n, rows, cols, _ = graph.rmat_graph(args.scale, args.edgefactor, 1)
    nw = n // 32
    rows, cols = jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32)

    @jax.jit
    def dedup(rows, cols):  # models/tc.py:_tc_edge_harvest_bits, tc.dedup
        rows, cols, dup = ops.coo_sort_dedup(rows, cols)
        keep = (rows > cols) & ~dup
        er, ec, ew, edges = ops.front_pack_pairs(keep, rows, cols, chunk=CHUNK)
        return jnp.where((rows == cols) | dup, n, rows), cols, er, ec, ew, edges

    (r_all, cols, er, ec, ew, edges), best, med, _ = timed(
        dedup, rows, cols, repeats=args.repeats)
    pairs = -(-int(edges) // CHUNK) * CHUNK
    moved = pairs * 2 * (n // 8)
    common = {
        "n": n, "edges": int(edges), "pairs": pairs, "gathered_gb": moved / 1e9,
        "platform": dev.platform, "device_kind": dev.device_kind,
    }
    os.makedirs(os.path.dirname(OUT), exist_ok=True)

    def emit(line):
        line.update(common)
        with open(OUT, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)

    emit({"rung": "dedup", "ms": best * 1e3, "median_ms": med * 1e3})
    ref, ok = None, True
    for table in args.tables:
        tiles = table != "rows"
        if tiles and nw % ops.TILE_WORDS:
            continue
        if table == "row_tiles_3idx":
            pack = jax.jit(lambda r, c: jnp.zeros(
                (n, nw // ops.LANES, ops.LANES), jnp.uint32,
            ).at[r, c >> 12, (c >> 5) & (ops.LANES - 1)].add(
                jnp.uint32(1) << (c.astype(jnp.uint32) & 31), mode="drop"))
        else:
            pack = jax.jit(lambda r, c, tiles=tiles: ops.pack_support_bits(
                r, c, n, n, assume_unique=True, row_tiles=tiles))
        # one table alive at a time: 8.59 GB at n = 2^18
        walls = []
        for _ in range(args.repeats + 1):
            t0 = time.perf_counter()
            bits = jax.block_until_ready(pack(r_all, cols))
            walls.append(time.perf_counter() - t0)
            if len(walls) <= args.repeats:
                bits.delete()
        emit({"rung": "pack", "table": table, "shape": list(bits.shape),
              "ms": min(walls[1:]) * 1e3,
              "median_ms": statistics.median(walls[1:]) * 1e3,
              "first_s": walls[0]})
        fused = tiles and ops._kernel_mode() is not None
        sizes = args.groups if fused else args.steps
        for size in () if table == "row_tiles_3idx" else sizes:
            # a fresh function a rung: the group is read at trace time
            if fused:
                ops.HARVEST_GROUP, step = size, CHUNK
            else:
                step = size
            harvest = jax.jit(lambda b, i, j, w, c, step=step:
                              ops.popcount_pair_counts(
                                  b, b, i, j, w, chunk=step, count=c))
            hilo, best, med, first = timed(
                harvest, bits, er, ec, ew, edges, repeats=args.repeats)
            total = ops.combine_hilo(hilo)
            ref = total if ref is None else ref
            ok &= total == ref
            stats = dev.memory_stats() or {}
            emit({"rung": "harvest", "table": table,
                  "route": "fused" if fused else "jnp",
                  "K" if fused else "T": size,
                  "ms": best * 1e3, "median_ms": med * 1e3, "first_s": first,
                  "step_us": best * 1e6 / (pairs // step),
                  "gbps": moved / best / 1e9,
                  "three_t": total, "same_as_first_rung": total == ref,
                  "peak_bytes": stats.get("peak_bytes_in_use")})
        bits.delete()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
