#!/usr/bin/env python
"""What ordering the triangle count's edge list costs by how it is
sorted, at the cell's shape: ``g500-s18-tc-1x1``'s stored list (n =
2^18, 7,611,536 slots, as ``chipbench/graph.py`` makes it and the
driver uploads it), ordered by ``(row, col)`` with the repeat mask, by
rung:

- ``argsort``: two stable ``argsort``s, columns then rows, each
  followed by two element gathers through its permutation
  (``coo_sort_dedup`` before PR 51);
- ``two_key``: ONE ``lax.sort((rows, cols), num_keys=2)``, stable and
  not (equal ``(row, col)`` slots carry nothing, so neither can be told
  from the other);
- ``two_pass``: TWO one-key sorts, least significant first, each
  carrying the other list: ``(cols, rows)`` then ``(rows, cols)``; the
  second is stable, the first stable and not (ties of the first pass
  are re-ordered by the second's key or are equal slots);
- ``shipped``: ``ops/spgemm.py:coo_sort_dedup`` as a job calls it.

    chiprun -- python scripts/tc_dedup_ladder.py
    JAX_PLATFORMS=cpu python scripts/tc_dedup_ladder.py --scale 12

Each time is the best and the median of ``--repeats`` runs after one
that compiles; ``ns_slot`` is the best over the list's slots.  One JSON
line a rung on stdout and in ``chiprun_out/tc_dedup_ladder.jsonl`` (with
the device it ran on: a CPU's times say nothing about the chip).  Every
rung's ``(rows, cols, dup)`` is held to the first's by a digest (each
list's sum under position-dependent odd multipliers, and the repeats
marked); a rung that differs exits 1.  ``--repeat-share`` writes that
share of the slots a second time and ``--sentinels`` that many slots at
row ``n``, so the mask and the dropped slots are in the digest too (the
cell's own list has neither).  Re-run before changing the form of
``coo_sort_dedup``.
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

from chipbench import graph
from combblas_tpu.ops import spgemm as ops
from combblas_tpu.utils import compile_cache

OUT = os.path.join("chiprun_out", "tc_dedup_ladder.jsonl")


def repeats_of(rows, cols):
    return jnp.concatenate([
        jnp.zeros((1,), bool),
        (rows[1:] == rows[:-1]) & (cols[1:] == cols[:-1]),
    ])


def argsort(rows, cols):
    order_c = jnp.argsort(cols, stable=True)
    r1, c1 = rows[order_c], cols[order_c]
    order_r = jnp.argsort(r1, stable=True)
    rows, cols = r1[order_r], c1[order_r]
    return rows, cols, repeats_of(rows, cols)


def two_key(stable):
    def dedup(rows, cols):
        rows, cols = lax.sort((rows, cols), num_keys=2, is_stable=stable)
        return rows, cols, repeats_of(rows, cols)
    return dedup


def two_pass(first_stable):
    def dedup(rows, cols):
        cols, rows = lax.sort((cols, rows), num_keys=1,
                              is_stable=first_stable)
        rows, cols = lax.sort((rows, cols), num_keys=1, is_stable=True)
        return rows, cols, repeats_of(rows, cols)
    return dedup


@jax.jit
def digest(rows, cols, dup):
    """(rows' and cols' weighted sums mod 2^32, repeats marked and their
    weighted sum), on the device."""
    at = lax.iota(jnp.uint32, rows.shape[0])
    odd = at * jnp.uint32(2654435761) | jnp.uint32(1)
    return (jnp.sum(rows.astype(jnp.uint32) * odd, dtype=jnp.uint32),
            jnp.sum(cols.astype(jnp.uint32) * odd, dtype=jnp.uint32),
            jnp.sum(dup.astype(jnp.int32)),
            jnp.sum(dup.astype(jnp.uint32) * odd, dtype=jnp.uint32))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=18)
    ap.add_argument("--edgefactor", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--repeat-share", type=float, default=0.0)
    ap.add_argument("--sentinels", type=int, default=0)
    ap.add_argument("--skip", nargs="*", default=[],
                    help="rungs to leave out: argsort two_key two_pass shipped")
    args = ap.parse_args()
    compile_cache.enable_compile_cache()
    dev = jax.devices()[0]
    n, rows, cols, _ = graph.rmat_graph(args.scale, args.edgefactor, 1)
    rows, cols = np.asarray(rows, np.int32), np.asarray(cols, np.int32)
    rng = np.random.default_rng(1)
    again = rng.choice(len(rows), int(args.repeat_share * len(rows)))
    rows = np.concatenate([rows, rows[again],
                           np.full(args.sentinels, n, np.int32)])
    cols = np.concatenate([cols, cols[again],
                           rng.integers(0, n, args.sentinels, np.int32)])
    if len(again) or args.sentinels:
        mix = rng.permutation(len(rows))
        rows, cols = rows[mix], cols[mix]
    rows, cols = jnp.asarray(rows), jnp.asarray(cols)
    slots = int(rows.shape[0])
    common = {"n": n, "slots": slots, "platform": dev.platform,
              "device_kind": dev.device_kind}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    state = {"ref": None, "ok": True}

    def rung(name, fn, **labels):
        fn = jax.jit(fn)
        walls = []
        try:
            for _ in range(args.repeats + 1):
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn(rows, cols))
                walls.append(time.perf_counter() - t0)
            dig = [int(v) for v in jax.device_get(digest(*out))]
        except Exception as e:  # a rung the compiler refuses is a finding
            line = {"rung": name, **labels, "error": repr(e)[:400]}
        else:
            state["ref"] = state["ref"] or dig
            state["ok"] &= dig == state["ref"]
            line = {"rung": name, **labels, "ms": min(walls[1:]) * 1e3,
                    "median_ms": statistics.median(walls[1:]) * 1e3,
                    "first_s": walls[0],
                    "ns_slot": min(walls[1:]) * 1e9 / slots,
                    "digest": dig, "same_as_first_rung": dig == state["ref"]}
        line.update(common)
        with open(OUT, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)

    if "argsort" not in args.skip:
        rung("argsort", argsort)
    if "two_key" not in args.skip:
        for stable in (False, True):
            rung("two_key", two_key(stable), stable=stable)
    if "two_pass" not in args.skip:
        for stable in (True, False):
            rung("two_pass", two_pass(stable), first_stable=stable)
    if "shipped" not in args.skip:
        rung("shipped", ops.coo_sort_dedup)
    return 0 if state["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
