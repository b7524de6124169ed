#!/usr/bin/env python
"""The grain of ``ops/spgemm.py:sparsify_windowed``'s sort, timed on ONE
window: for each candidate ``SPARSIFY_GROUP_CELLS`` the sort of the
window's ``[G, L]`` (key, value) pairs, the copy that lays the groups'
prefixes end to end (``_lay_prefixes``) and the whole extraction, each
the best and the median of ``--repeats`` runs after one that compiles.

    chiprun -- python scripts/sparsify_ladder.py            # [4096, 8192], 15.2% set
    JAX_PLATFORMS=cpu python scripts/sparsify_ladder.py --rows 64 --cols 128

One JSON line a rung on stdout and in ``chiprun_out/sparsify_ladder.jsonl``
(with the device it ran on: a CPU's times say nothing about the chip).
Every rung's tuples are held to the flat sort's; a rung that differs
exits 1.  Start the next change to the extraction from here: a window
costs a job eight times what it costs this script once.
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
import numpy as np
from jax import lax

from combblas_tpu.ops import spgemm as ops
from combblas_tpu.utils import compile_cache

OUT = os.path.join("chiprun_out", "sparsify_ladder.jsonl")


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
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--cols", type=int, default=8192)
    ap.add_argument("--fill", type=float, default=0.152)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument(
        "--group-cells", type=int, nargs="*", default=None,
        help="rungs (default: the window's cells, then 2^22 ... one row)")
    args = ap.parse_args()
    compile_cache.enable_compile_cache()
    R, C = args.rows, args.cols
    cells = R * C
    rungs = args.group_cells or sorted(
        {cells} | {c for c in (1 << 22, 1 << 20, 1 << 19, 1 << 18, 1 << 17,
                               1 << 16, 1 << 15, 1 << 14)
                   if C <= c < cells} | {C},
        reverse=True)
    dev = jax.devices()[0]
    k1, k2 = jax.random.split(jax.random.PRNGKey(args.seed))
    dense = jnp.where(
        jax.random.uniform(k1, (R, C)) < args.fill,
        jnp.floor(jax.random.uniform(k2, (R, C)) * 100) + 1, 0.0,
    ).astype(jnp.float32)
    flat = dense.reshape(-1)
    key = jnp.where(flat != 0, jnp.arange(cells, dtype=jnp.int32), cells)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    ref, ok = None, True
    for gc in rungs:
        ops.SPARSIFY_GROUP_CELLS = gc
        G = ops.sparsify_groups(R, C)
        # as the library sorts: flat for one group (the same cells as
        # [1, cells] along axis 1 read 426 ms where the flat sort is 76)
        shape = (G, -1) if G > 1 else (-1,)
        sort = jax.jit(lambda k, v, shape=shape: lax.sort(
            (k.reshape(shape), v.reshape(shape)), dimension=len(shape) - 1,
            num_keys=1, is_stable=False))
        (sk, sv), sort_best, sort_med, sort_first = timed(
            sort, key, flat, repeats=args.repeats)
        line = {
            "group_cells": gc, "groups": G, "rows_a_group": R // G,
            "sort_ms": sort_best * 1e3, "sort_median_ms": sort_med * 1e3,
            "sort_first_s": sort_first,
        }
        if G > 1:
            _, best, med, _ = timed(
                jax.jit(ops._lay_prefixes), sk, sv, repeats=args.repeats)
            line.update(copy_ms=best * 1e3, copy_median_ms=med * 1e3)
        del sk, sv
        # a fresh function a rung: the grain is read at trace time
        whole = jax.jit(lambda d: ops.sparsify_windowed(d, 0.0, R, C, cells))
        (t, total), best, med, first = timed(
            whole, dense, repeats=args.repeats)
        got = tuple(np.asarray(x) for x in (t.rows, t.cols, t.vals, t.nnz, total))
        if ref is None:
            ref = got
        same = all(np.array_equal(a, b) for a, b in zip(got, ref))
        ok &= same
        stats = dev.memory_stats() or {}
        line.update(
            extract_ms=best * 1e3, extract_median_ms=med * 1e3,
            extract_first_s=first, total=int(total), same_as_first_rung=same,
            peak_bytes=stats.get("peak_bytes_in_use"),
            window=[R, C], fill=args.fill, seed=args.seed,
            platform=dev.platform, device_kind=dev.device_kind,
        )
        del t, total
        with open(OUT, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
