"""A served BFS level gathers WHO is in the frontier, not who they are
(PR 35: ``models.bfs._bfs_batch_tallied`` carries the frontier as
membership bits, ``ellmat.ell_frontier_sweep`` gathers one int32 word a
column for every 32 lanes and makes the candidate parent from the slot's
own column id).  Held here, entry for entry, to a plain numpy BFS that
picks the largest in-frontier in-neighbour id: parents, levels, ``niter``,
the tally of class sweeps by what each tile's device chose, and which
levels it walked instead (``ellmat.ell_frontier_push``: those whose
frontiers' columns fit ``CAPACITY`` edges on every tile), with the
companion and without it."""

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu.models import PAD_ROOT
from combblas_tpu.models import bfs as bfs_mod
from combblas_tpu.parallel import ellmat
from combblas_tpu.parallel.grid import Grid

from conftest import idle_classes, push_capacity, pushed_levels

GRIDS = [(1, 1), (2, 2), (2, 4), (4, 2)]
WIDTHS = [1, 4, 16, 64]  # 64: two membership words a column

#: ``PUSH_EDGE_CAPACITY`` for this module's programs: the roots' level
#: and a search's thin last levels fit it, the wide ones between do not
CAPACITY = 40

#: the tie's vertices: root R reaches A (the first column block of every
#: grid here) and B (the last) at level 1, and V hears from both at level 2
R, A, B, V = 100, 3, 200, 50


@lru_cache(maxsize=None)
def _graph(name):
    """``(rows, cols, n)``: entry (i, j) is the edge j -> i.  Directed and
    random (a parent is an IN-neighbour), ``ragged``'s ``n`` = 203 no
    multiple of any grid here, its last vertex with out-edges, and the
    tie's four vertices cut off from the rest."""
    n = {"even": 256, "ragged": 203}[name]
    rng = np.random.default_rng(n)
    rows = rng.integers(0, n, 5 * n)
    cols = rng.integers(0, n, 5 * n)
    if name == "ragged":
        keep = ~np.isin(rows, (R, A, B, V)) & ~np.isin(cols, (R, A, B, V))
        rows = np.concatenate([rows[keep], [A, B, V, V], [7, 90, 150]])
        cols = np.concatenate([cols[keep], [R, R, A, B], [n - 1] * 3])
    keep = rows != cols
    pairs = np.unique(np.stack([rows[keep], cols[keep]]), axis=1)
    return pairs[0], pairs[1], n


@lru_cache(maxsize=None)
def _operands(name, shape):
    """``(E, companion)`` of the graph on the grid, built once."""
    rows, cols, n = _graph(name)
    grid = Grid.make(*shape)
    E = ellmat.EllParMat.from_host_coo(
        grid, rows, cols, np.ones(len(rows), np.float32), n, n)
    indptr, rowidx = ellmat.build_csc_companion(grid, rows, cols, n, n)
    return E, (indptr, rowidx, jnp.asarray(True))


@jax.jit
def _all_pull(E, roots):
    return bfs_mod._bfs_batch_tallied(E, roots, None, True)


@jax.jit
def _pushing_program(E, csc, roots):
    return bfs_mod._bfs_batch_tallied(E, roots, None, True, csc)


def _pushing(E, csc, roots):
    with push_capacity(CAPACITY):  # read when the program is traced
        return _pushing_program(E, csc, roots)


def _roots(case, name, width):
    rows, cols, n = _graph(name)
    out = np.flatnonzero(np.bincount(cols, minlength=n))  # has out-edges
    roots = out[np.linspace(0, len(out) - 1, width).astype(int)]
    if case == "pad_lanes":
        roots[width // 2:] = PAD_ROOT  # width 1: the whole batch is pad
    elif case == "last_block":
        roots[-1] = n - 1
    elif case == "tie":
        roots[-1] = R
    return roots.astype(np.int32)


def _numpy_bfs(rows, cols, n, roots):
    """Level-synchronous BFS a lane, the largest in-frontier in-neighbour
    id for a parent.  Returns ``(parents, levels, niter, history)``:
    ``history[k]`` is ``(frontier, unvisited)`` as iteration ``k`` of the
    batch's loop finds them, the iteration that finds nothing included."""
    W = len(roots)
    parents = np.full((n, W), -1, np.int32)
    levels = np.full((n, W), -1, np.int32)
    frontier = np.zeros((n, W), bool)
    for lane, r in enumerate(roots):
        if r != PAD_ROOT:
            parents[r, lane], levels[r, lane] = r, 0
            frontier[r, lane] = True
    history = []
    while True:
        history.append((frontier, parents < 0))
        cand = np.full((n, W), -1, np.int32)
        for lane in range(W):
            e = frontier[cols, lane]
            np.maximum.at(cand[:, lane], rows[e], cols[e])
        new = (cand >= 0) & (parents < 0)
        parents[new] = cand[new]
        levels[new] = len(history)
        frontier = new
        if not new.any():
            return parents, levels, len(history), history


def _numpy_tally(E, history):
    """``[pr, pc, classes, 2]``: each tile's and degree class's sweeps
    run dense / skipped over the loop's iterations ``history``: a tile
    skips a degree class none of whose bucket rows is unvisited in a lane
    with a frontier vertex in the tile's column block
    (``conftest.idle_classes``)."""
    skipped = sum(
        (idle_classes(E, frontier, unvisited) for frontier, unvisited in history),
        np.zeros((E.grid.pr, E.grid.pc, len(E.buckets)), np.int64))
    return np.stack([len(history) - skipped, skipped], axis=-1).tolist()


def _lanes(blocks, n):
    return np.asarray(blocks).reshape(-1, blocks.shape[-1])[:n]


@pytest.mark.parametrize("case,name", [
    ("spread", "even"), ("spread", "ragged"), ("pad_lanes", "ragged"),
    ("last_block", "ragged"), ("tie", "ragged"),
])
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("shape", GRIDS, ids=lambda s: f"{s[0]}x{s[1]}")
def test_a_level_of_bits_is_the_numpy_bfs(shape, width, case, name):
    rows, cols, n = _graph(name)
    E, companion = _operands(name, shape)
    roots = _roots(case, name, width)
    parents, levels, niter, history = _numpy_bfs(rows, cols, n, roots)
    if case == "tie":  # decided between two column blocks: the larger id
        assert parents[V, -1] == B and levels[V, -1] == 2
        assert parents[A, -1] == parents[B, -1] == R
    if case == "last_block":
        assert np.all(levels[[7, 90, 150], -1] == 1)  # its own edges

    pulled = _all_pull(E, jnp.asarray(roots))
    pushed = _pushing(E, companion, jnp.asarray(roots))
    assert pulled[4] is None  # no companion: no push in the program
    walks = pushed_levels(E, rows, cols, history, CAPACITY)
    report = pushed[4]
    assert bfs_mod.PUSH_OUTCOMES[int(report.outcome)] == (
        "over_budget" if walks[0] is None else "taken")
    assert int(report.levels) == sum(w is not None for w in walks)
    assert np.asarray(report.edges).tolist() == sum(
        w for w in walks if w is not None).tolist()
    # a level taken as a push sweeps no degree class
    swept = [h for h, w in zip(history, walks) if w is None]
    for got, loop in ((pulled, history), (pushed, swept)):
        np.testing.assert_array_equal(_lanes(got[0], n), parents)
        np.testing.assert_array_equal(_lanes(got[1], n), levels)
        assert int(got[2]) == niter
        assert np.asarray(got[3]).tolist() == _numpy_tally(E, loop)
        # rows past n (the grid's padding) are nobody's
        assert np.all(np.asarray(got[0]).reshape(-1, width)[n:] == -1)


def test_a_stale_companion_leaves_every_level_to_the_sweep():
    rows, cols, n = _graph("ragged")
    E, (indptr, rowidx, _) = _operands("ragged", (2, 2))
    roots = _roots("tie", "ragged", 16)
    parents, levels, niter, history = _numpy_bfs(rows, cols, n, roots)
    got = _pushing(E, (indptr, rowidx, jnp.asarray(False)),
                   jnp.asarray(roots))
    assert int(got[4].outcome) == bfs_mod.PUSH_OUTCOMES.index("stale")
    assert int(got[4].levels) == 0 and not np.any(np.asarray(got[4].edges))
    np.testing.assert_array_equal(_lanes(got[0], n), parents)
    np.testing.assert_array_equal(_lanes(got[1], n), levels)
    assert int(got[2]) == niter
    assert np.asarray(got[3]).tolist() == _numpy_tally(E, history)


@pytest.mark.parametrize("width", [1, 4, 16, 31, 32, 33, 64, 70])
def test_lanes_pack_32_to_a_word_and_back(width):
    rng = np.random.default_rng(width)
    mask = rng.random((3, 5, width)) < 0.4
    mask[0, 0] = True  # every bit of a word, the sign bit with them
    mask[0, 1] = False
    words = ellmat.pack_lanes(jnp.asarray(mask))
    assert words.dtype == jnp.int32
    assert words.shape == (3, 5, -(-width // ellmat.WORD_LANES))
    for lane in range(width):
        w, bit = divmod(lane, ellmat.WORD_LANES)
        got = (np.asarray(words)[..., w].astype(np.int64) >> bit) & 1
        np.testing.assert_array_equal(got.astype(bool), mask[..., lane])
    np.testing.assert_array_equal(
        np.asarray(ellmat.unpack_lanes(words, width)), mask)


def test_the_table_a_tile_gathers_from_is_a_word_a_column():
    E, _ = _operands("ragged", (2, 4))
    assert bfs_mod.FRONTIER_PAYLOAD == "bits"
    for width, words in ((1, 1), (16, 1), (32, 1), (33, 2), (64, 2)):
        assert bfs_mod.frontier_table_bytes(E, width) == (
            4 * (E.local_cols + 1) * words)
