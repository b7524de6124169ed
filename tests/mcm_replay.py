"""``models/matching.py:mcm_job`` over a ``BipartiteEll``, replayed on
the host in numpy: the same rounds, layers, ties and winner selection,
and for every step (a round's proposals, a round's free degrees, a
phase's layer) whether the device walks it or sweeps it and the edges a
walk holds on each tile.  Independent of the program but for the tile
geometry (``Grid.local_rows`` / ``local_cols``: block lengths).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Step(NamedTuple):
    where: str  # "init" | "bfs"
    way: str  # "AT": rows towards columns; "A": columns towards rows
    edges: np.ndarray  # int64[p, p], what each tile's walk would hold
    push: bool  # walked (every tile fits the capacity), else swept


class Replay(NamedTuple):
    mate_row: np.ndarray
    mate_col: np.ndarray
    init_rounds: int
    init_matched: int
    phases: int
    steps: list


def _blocks(n: int, p: int) -> int:
    return -(-n // p)


def replay(rows, cols, nr: int, nc: int, p: int, capacity: int) -> Replay:
    """The job on a ``p`` x ``p`` grid with a walk's ``capacity``."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    Lr, Lc = _blocks(nr, p), _blocks(nc, p)
    steps = []

    def step(where, way, inside):
        """``inside``: bool over the side the walk leaves from."""
        if way == "AT":  # AT [nc, nr]: tile (col block, row block)
            sel = inside[rows]
            tile = (cols[sel] // Lc) * p + rows[sel] // Lr
            L = Lr
        else:  # A [nr, nc]: tile (row block, col block)
            sel = inside[cols]
            tile = (rows[sel] // Lr) * p + cols[sel] // Lc
            L = Lc
        # (columns without an edge are columns of a walk all the same)
        held = max(int(inside[j * L:(j + 1) * L].sum()) for j in range(p))
        edges = np.bincount(tile, minlength=p * p).reshape(p, p)
        push = bool(edges.max() <= capacity and held <= capacity)
        steps.append(Step(where, way, edges, push))
        return sel

    def largest(inside):
        """For every column the largest adjacent row of ``inside``."""
        sel = inside[rows]
        out = np.full(nc, -1, np.int64)
        np.maximum.at(out, cols[sel], rows[sel])
        return out

    mr = np.full(nr, -1, np.int64)
    mc = np.full(nc, -1, np.int64)
    deg_free = np.bincount(rows, minlength=nr)
    has_edge = deg_free > 0
    KS, ALL, DONE = 0, 1, 2
    mode, rounds = KS, 0
    while mode != DONE:
        free_c = mc < 0
        proposes = (mr < 0) & (deg_free == 1 if mode == KS else deg_free >= 1)
        step("init", "AT", proposes)
        granted = np.where(free_c, largest(proposes), -1)
        got = np.full(nr, -1, np.int64)
        cs = np.flatnonzero(granted >= 0)
        np.maximum.at(got, granted[cs], cs)
        taken = np.zeros(nc, bool)
        taken[cs] = got[granted[cs]] == cs
        mr = np.where(got >= 0, got, mr)
        mc = np.where(taken, granted, mc)
        sel = step("init", "A", taken)
        deg_free = deg_free - np.bincount(rows[sel], minlength=nr)
        mode = KS if taken.any() else (ALL if mode == KS else DONE)
        rounds += 1
    init_matched = int((mc >= 0).sum())

    phases = 0
    while True:
        phases += 1
        frontier = mr < 0
        parent = np.full(nc, -1, np.int64)
        seen = np.zeros(nc, bool)
        found, depth = False, 0
        while not found and frontier.any() and depth < nr + 2:
            step("bfs", "AT", frontier & has_edge)
            reach = largest(frontier)
            new = (reach >= 0) & ~seen
            parent[new] = reach[new]
            seen |= new
            found = bool((new & (mc < 0)).any())
            frontier = np.zeros(nr, bool)
            frontier[mc[new & (mc >= 0)]] = True
            depth += 1
        cand = np.flatnonzero(seen & (mc < 0)) if found else []
        chains = {}
        for j in cand:
            chain, cur = [], j
            while True:
                r = parent[cur]
                chain.append((r, cur))
                if mr[r] < 0:
                    break
                cur = mr[r]
            chains[j] = chain
        claims = {}
        for j, chain in chains.items():  # the smallest path id wins a row
            for r, _ in chain:
                claims[r] = min(claims.get(r, j), j)
        augmented = 0
        for j, chain in chains.items():
            if all(claims[r] == j for r, _ in chain):
                for r, c in chain:
                    mr[r], mc[c] = c, r
                augmented += 1
        if augmented == 0:
            break
    return Replay(mr, mc, rounds, init_matched, phases, steps)
