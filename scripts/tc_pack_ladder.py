#!/usr/bin/env python
"""What packing the triangle count's bit table costs by how it is
written, at the cell's shapes: ``g500-s18-tc-1x1``'s graph (n = 2^18),
its stored slots sorted and marked as ``models/tc.py`` hands them to
``pack_support_bits`` (loops and repeats at row ``n``, inside the sorted
order), then the table ``uint32[n * 64, 128]`` by rung:

- ``scatter``: the zero fill and the scatter-add of one bit a slot into
  HBM (the pack before PR 49, and every other caller's still), plain
  and with ``indices_are_sorted`` / ``unique_indices`` promised;
- ``slabs``: a loop over slabs of ``S`` rows (``--slabs``), each zeroed,
  scatter-added from its own slots of the list and laid into the table,
  no kernel: whether the compiler keeps a slab in its fast memory;
- ``offsets``: what the kernel's operands cost alone
  (``ops/spgemm.py:pack_rows_operands``: a slot's sublane and place in
  it, the fill from the left and ``n / G + 1`` binary searches);
- ``kernel``: ``pallas_kernels.pack_rows`` (operands included) by rows a
  group ``G``, slots a piece ``P`` and slots an unrolled chunk ``U``
  (``--kernel G:P:U ...``);
- ``shipped``: ``pack_support_bits(row_tiles=True)`` as a job calls it.

    chiprun -- python scripts/tc_pack_ladder.py
    JAX_PLATFORMS=cpu python scripts/tc_pack_ladder.py --scale 15 --kernel 8:1024:4

Each time is the best and the median of ``--repeats`` runs after one
that compiles; ``ns_slot`` is the best over the list's slots.  One JSON
line a rung on stdout and in ``chiprun_out/tc_pack_ladder.jsonl`` (with
the device it ran on: a CPU's times say nothing about the chip, and a
CPU interprets the kernel).  Every rung's table is held to the first's
by a digest (the words' sum under position-dependent odd multipliers,
and the bits set); a rung that differs exits 1.  Re-run before moving
``ops/spgemm.py:PACK_GROUP`` / ``PACK_PIECE`` or the kernel's unroll.
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
from jax import lax

from chipbench import graph
from combblas_tpu.ops import pallas_kernels
from combblas_tpu.ops import spgemm as ops
from combblas_tpu.utils import compile_cache

OUT = os.path.join("chiprun_out", "tc_pack_ladder.jsonl")
LANES = ops.LANES


@jax.jit
def digest(bits):
    """(weighted sum mod 2^32, bits set) of a table, on the device."""
    flat = bits.reshape(-1, LANES)
    at = (lax.broadcasted_iota(jnp.uint32, flat.shape, 0) * LANES
          + lax.broadcasted_iota(jnp.uint32, flat.shape, 1))
    odd = at * jnp.uint32(2654435761) | jnp.uint32(1)
    ones = jnp.sum(lax.population_count(flat).astype(jnp.int32))
    return jnp.sum(flat * odd, dtype=jnp.uint32), ones


def bit_of(c):
    return jnp.uint32(1) << (c.astype(jnp.uint32) & 31)


def scatter(n, tiles, **flags):
    def pack(r, c):
        word = c >> 5
        return jnp.zeros((n * tiles, LANES), jnp.uint32).at[
            r * tiles + (word >> 7), word & (LANES - 1)
        ].add(bit_of(c), mode="drop", **flags)
    return pack


def slabs(n, tiles, slab, trip=65536):
    """No kernel: slab ``s`` is zeroed, scatter-added from the slots
    between its two offsets, ``trip`` at a time, and laid into the
    table."""
    def pack(r, c):
        filled = lax.cummax(jnp.where(r < n, r, 0))
        off = jnp.searchsorted(
            filled, jnp.arange(n // slab + 1, dtype=jnp.int32) * slab
        ).astype(jnp.int32)
        r = jnp.pad(r, (0, trip), constant_values=n)
        c = jnp.pad(c, (0, trip))

        def one(s, table):
            lo, hi = off[s], off[s + 1]

            def some(t, sb):
                at = lo + t * trip
                rr = lax.dynamic_slice(r, (at,), (trip,)) - s * slab
                cc = lax.dynamic_slice(c, (at,), (trip,))
                ok = ((at + jnp.arange(trip) < hi) & (rr >= 0) & (rr < slab))
                word = cc >> 5
                return sb.at[
                    jnp.where(ok, rr * tiles + (word >> 7), slab * tiles),
                    word & (LANES - 1),
                ].add(bit_of(cc), mode="drop")

            sb = lax.fori_loop(
                0, -(-(hi - lo) // trip), some,
                jnp.zeros((slab * tiles, LANES), jnp.uint32))
            return lax.dynamic_update_slice(table, sb, (s * slab * tiles, 0))

        return lax.fori_loop(
            0, n // slab, one, jnp.zeros((n * tiles, LANES), jnp.uint32))
    return pack


def kernel(n, nw, group, piece, unroll):
    def pack(r, c):
        return pallas_kernels.pack_rows(
            *ops.pack_rows_operands(r, c, n, nw, group=group, piece=piece),
            n, nw, group=group, piece=piece, unroll=unroll,
            interpret=ops._kernel_mode() != "compiled")
    return pack


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=18)
    ap.add_argument("--edgefactor", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--slabs", type=int, nargs="*", default=[2048, 512])
    ap.add_argument("--kernel", nargs="*", default=[
        "32:2048:16", "32:2048:1", "32:2048:4", "32:2048:8", "32:2048:32",
        "8:2048:16", "16:2048:16", "64:2048:16", "32:1024:16", "32:8192:16"],
        help="G:P:U rungs of the kernel")
    ap.add_argument("--skip", nargs="*", default=[],
                    help="rungs to leave out: scatter slabs offsets shipped")
    args = ap.parse_args()
    compile_cache.enable_compile_cache()
    dev = jax.devices()[0]
    n, rows, cols, _ = graph.rmat_graph(args.scale, args.edgefactor, 1)
    nw = n // 32
    assert nw % ops.TILE_WORDS == 0, "a row must be whole tiles: scale >= 15"
    tiles = nw // LANES
    rows, cols = jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32)

    @jax.jit
    def dedup(rows, cols):  # models/tc.py:_tc_edge_harvest_bits, tc.dedup
        rows, cols, dup = ops.coo_sort_dedup(rows, cols)
        return jnp.where((rows == cols) | dup, n, rows), cols

    r_all, cols = jax.block_until_ready(dedup(rows, cols))
    slots = int(r_all.shape[0])
    common = {
        "n": n, "slots": slots, "table_gb": n * nw * 4 / 1e9,
        "platform": dev.platform, "device_kind": dev.device_kind,
    }
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    state = {"ref": None, "ok": True}

    def emit(line):
        line.update(common)
        with open(OUT, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)

    def rung(name, fn, table=True, **labels):
        """Time ``fn(r_all, cols)``; one table alive at a time (8.59 GB
        at n = 2^18)."""
        fn = jax.jit(fn)
        walls, dig = [], None
        try:
            for _ in range(args.repeats + 1):
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn(r_all, cols))
                walls.append(time.perf_counter() - t0)
                if table and dig is None:
                    dig = [int(v) for v in jax.device_get(digest(out))]
                for leaf in jax.tree_util.tree_leaves(out):
                    leaf.delete()
        except Exception as e:  # a rung the compiler refuses is a finding
            emit({"rung": name, **labels, "error": repr(e)[:400]})
            return
        line = {"rung": name, **labels, "ms": min(walls[1:]) * 1e3,
                "median_ms": statistics.median(walls[1:]) * 1e3,
                "first_s": walls[0], "ns_slot": min(walls[1:]) * 1e9 / slots}
        if table:
            state["ref"] = state["ref"] or dig
            line.update(digest=dig, same_as_first_rung=dig == state["ref"])
            state["ok"] &= dig == state["ref"]
        stats = dev.memory_stats() or {}
        line["peak_bytes"] = stats.get("peak_bytes_in_use")
        emit(line)

    if "scatter" not in args.skip:
        rung("scatter", scatter(n, tiles), flags="none")
        rung("scatter", scatter(n, tiles, indices_are_sorted=True,
                                unique_indices=True), flags="sorted+unique")
    if "slabs" not in args.skip:
        for slab in args.slabs:
            rung("slabs", slabs(n, tiles, slab), S=slab)
    timed_operands = set()  # the operands depend on G and P alone
    for spec in args.kernel:
        group, piece, unroll = (int(v) for v in spec.split(":"))
        if ("offsets" not in args.skip
                and (group, piece) not in timed_operands):
            timed_operands.add((group, piece))
            rung("offsets", lambda r, c: ops.pack_rows_operands(
                r, c, n, nw, group=group, piece=piece),
                table=False, G=group, P=piece)
        rung("kernel", kernel(n, nw, group, piece, unroll),
             G=group, P=piece, U=unroll)
    if "shipped" not in args.skip:
        rung("shipped", lambda r, c: ops.pack_support_bits(
            r, c, n, n, assume_unique=True, row_tiles=True),
            G=ops.PACK_GROUP, P=ops.PACK_PIECE,
            path="rows" if ops._kernel_mode() else "scatter")
    return 0 if state["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
