#!/usr/bin/env python
"""What one level of a served BFS costs as a walk of its frontier's
columns, at the deep-graph cell's shape: ``rgg-n20-1x1``'s graph (as
``chipbench/rgggraph.py`` makes it), 16 lanes, the frontiers of a real
batch at a few of its levels (scipy's, from ``draw_roots``), by rung:

- ``fit``: ``ellmat.ell_frontier_fit`` (every level pays it);
- ``push``: ``ellmat.ell_frontier_push`` as the plan calls it, at the
  trip sizes ``--slot-chunk`` / ``--column-chunk`` name (the shipped
  ones first);
- ``sweep``: ``ellmat.ell_frontier_sweep`` of the same level with no
  row visited (what the level cost before it could be walked);
- ``update``: the loop's own ``[n, W]`` work on the level's candidates
  (``bfs.update``, ``pack_lanes``, ``bfs.active``);
- ``wave`` (``--rungs wave``; not run unless named): the whole search
  of the batch as the served plan runs it (``models.bfs.
  _bfs_batch_tallied`` handed the companion), every level's hop count
  held to scipy's: what a wave of the cell would cost at this size,
  with its levels, walked edges and scatter passes;
- ``trip`` (needs no graph; ``--rungs trip`` runs it alone): one pass of
  one trip of the walk's scatter, ``PUSH_SLOT_CHUNK`` single words
  folded by max into the flat ``[W * n]`` candidates, by the share of
  its indices that are in range (the rest at ``W * n``, dropped, as a
  slot whose lanes are spent is): what a pass over a trip costs when
  nearly no slot of it holds anything.

    chiprun -- python scripts/bfs_push_ladder.py
    JAX_PLATFORMS=cpu python scripts/bfs_push_ladder.py --n-log2 12 --repeats 1

Each time is the best and the median of ``--repeats`` runs after one
that compiles; ``ns_edge`` is the best over the edges the level's
frontiers hold, ``slots_edge`` the slots the walk scattered (its passes
times the trip's slots) over the same.  One JSON line a rung on stdout and in
``chiprun_out/bfs_push_ladder.jsonl`` (with the device it ran on: a
CPU's times say nothing about the chip).  Every ``push`` rung's
candidates are held to the sweep's (no row visited, so entry for
entry); a level that does not fit the capacity is not walked.
Re-run before moving ``PUSH_SLOT_CHUNK``, ``PUSH_COLUMN_CHUNK`` or
``models.bfs.PUSH_EDGE_CAPACITY``.
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

from chipbench import graph, rgggraph
from combblas_tpu.models import bfs as bfs_mod
from combblas_tpu.parallel import ellmat
from combblas_tpu.parallel.grid import Grid


def timed(fn, args, repeats):
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return out, 1e3 * min(times), 1e3 * statistics.median(times)


#: shares of a trip's indices in range that ``trip`` times: a first
#: pass, a second pass on the deep graph (about 300 of 16,384 slots sit
#: in a column two lanes share), a pass with nothing left
TRIP_SHARES = (1.0, 0.02, 0.0)


def trip_rungs(say, n, width, repeats, trips=1024):
    """Time ``trips`` passes of one trip's scatter in one program, by the
    share of indices in range; the program is the same for every share
    (the share is an operand)."""
    size, ktrip = width * n, ellmat.PUSH_SLOT_CHUNK
    rng = np.random.default_rng(1)
    where = jnp.asarray(rng.integers(0, size, ktrip, dtype=np.int32))
    draw = jnp.asarray(rng.random(ktrip, dtype=np.float32))

    @jax.jit
    def run(share):
        def one(k, y):
            # (moved by the trip, so that no pass is hoisted or merged)
            at = jnp.where(draw < share, (where + k * 7919) % size, size)
            return y.at[at].max(where + k, mode="drop")

        return lax.fori_loop(0, trips, one, jnp.full((size,), -1, jnp.int32))

    for share in TRIP_SHARES:
        _, best, med = timed(run, (jnp.float32(share),), repeats)
        say(rung="trip", share_in_range=share, slots=ktrip, trips=trips,
            table_words=size, best_ms=best, med_ms=med,
            us_pass=1e3 * best / trips, ns_slot=1e6 * best / trips / ktrip)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n-log2", type=int, default=20)
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--levels", type=float, nargs="*",
                    default=[0.0, 0.5, -1.0],
                    help="levels to time, as shares of the batch's depth "
                         "(-1: its fullest level)")
    ap.add_argument("--slot-chunk", type=int, nargs="*",
                    default=[1 << 14, 1 << 12, 1 << 16])
    ap.add_argument("--column-chunk", type=int, nargs="*",
                    default=[1 << 12, 1 << 10, 1 << 14])
    ap.add_argument("--rungs", nargs="*", default=["trip", "level"],
                    choices=["trip", "level", "wave"],
                    help="trip: the scatter of one trip alone; level: "
                         "fit / sweep / update / push of real levels; "
                         "wave: the batch's whole search")
    args = ap.parse_args()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    lines = []

    def say(**rec):
        rec.update(device=device, n_log2=args.n_log2, width=args.width)
        lines.append(rec)
        print(json.dumps(rec), flush=True)

    def write():
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/bfs_push_ladder.jsonl", "w") as f:
            f.writelines(json.dumps(rec) + "\n" for rec in lines)

    if "trip" in args.rungs:
        trip_rungs(say, 1 << args.n_log2, args.width, args.repeats)
        write()
    if not {"level", "wave"} & set(args.rungs):
        return 0  # the other rungs need the graph
    n, rows, cols, keys = rgggraph.rgg_graph(args.n_log2, 1)
    ref = graph.Reference(n, rows, cols, keys)
    roots = graph.draw_roots(ref.deg, args.seed, args.width)
    levels = np.stack([ref.bfs_levels(int(r)) for r in roots], axis=1)
    depth = int(levels.max()) + 1
    edges_by_level = np.asarray([
        int(ref.deg[(levels == k).any(axis=1)].sum()) for k in range(depth)])
    grid = Grid.make(1, 1)
    E = ellmat.EllParMat.from_host_coo(
        grid, rows, cols, np.ones(len(rows), np.float32), n, n)
    csc = ellmat.build_csc_companion(grid, rows, cols, n, n)
    coldeg, indptr, rowidx = ellmat.tile_lines(
        grid, csc[0][..., 1:] - csc[0][..., :-1], *csc)
    capacity = bfs_mod.push_capacity(E)
    bad = 0
    if "wave" in args.rungs:
        wave = jax.jit(lambda src: bfs_mod._bfs_batch_tallied(
            E, src, None, True, (*csc, True)))
        (_, got, niter, _, report), best, med = timed(
            wave, (jnp.asarray(roots),), min(args.repeats, 2))
        same = bool(np.array_equal(np.asarray(got)[0], levels))
        bad += not same
        walked = int(np.sum(report.edges))
        passes = int(np.sum(report.passes))
        say(rung="wave", best_ms=best, med_ms=med, levels=int(niter),
            push_levels=int(report.levels), edges=walked, passes=passes,
            slots_edge=(passes * min(ellmat.PUSH_SLOT_CHUNK, capacity)
                        / max(walked, 1)),
            ns_edge=1e6 * best / max(walked, 1), same=same)
        write()
    if "level" not in args.rungs:
        return 1 if bad else 0

    fit = jax.jit(lambda m: ellmat.ell_frontier_fit(E, coldeg, m, capacity))
    sweep = jax.jit(lambda m, act: ellmat.ell_frontier_sweep(E, m, act)[0])

    @jax.jit
    def update(y, parents, lv):
        new = (y >= 0) & (parents < 0)
        return (jnp.where(new, y, parents), jnp.where(new, 7, lv),
                ellmat.pack_lanes(new), jnp.any(new))

    chunks = [(args.slot_chunk[0], args.column_chunk[0])] + [
        (s, args.column_chunk[0]) for s in args.slot_chunk[1:]] + [
        (args.slot_chunk[0], c) for c in args.column_chunk[1:]]
    pushes = {c: jax.jit(lambda m: ellmat.ell_frontier_push(
        E, indptr, rowidx, m, args.width, capacity)) for c in chunks}
    unvisited = jnp.ones((1, n, args.width), jnp.bool_)
    for share in args.levels:
        k = int(np.argmax(edges_by_level)) if share < 0 else min(
            int(share * depth), depth - 1)
        member = ellmat.pack_lanes(jnp.asarray(levels == k))[None]
        edges = int(edges_by_level[k])
        common = dict(level=k, depth=depth, edges=edges,
                      columns=int((levels == k).any(axis=1).sum()))
        (fits, walked), best, med = timed(fit, (member,), args.repeats)
        say(rung="fit", fits=bool(fits), walked=int(np.sum(walked)),
            best_ms=best, med_ms=med, **common)
        want, best, med = timed(sweep, (member, unvisited), args.repeats)
        say(rung="sweep", best_ms=best, med_ms=med,
            ns_edge=1e6 * best / max(edges, 1), **common)
        parents = jnp.where(want >= 0, -1, 0)  # a stand-in of the shape
        _, best, med = timed(update, (want, parents, parents), args.repeats)
        say(rung="update", best_ms=best, med_ms=med, **common)
        for slot_chunk, column_chunk in chunks if bool(fits) else ():
            # (static: read when a rung's program is traced, at its
            # first level)
            ellmat.PUSH_SLOT_CHUNK = slot_chunk
            ellmat.PUSH_COLUMN_CHUNK = column_chunk
            (got, passes), best, med = timed(
                pushes[slot_chunk, column_chunk], (member,), args.repeats)
            same = bool(jnp.array_equal(got, want))
            bad += not same
            passes = int(np.sum(passes))
            say(rung="push", slot_chunk=slot_chunk,
                column_chunk=column_chunk, best_ms=best, med_ms=med,
                ns_edge=1e6 * best / max(edges, 1), passes=passes,
                slots_edge=passes * min(slot_chunk, capacity) / max(edges, 1),
                same=same, **common)
    write()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
