"""The order a walked level's columns go in (PR 53).
``ellmat.ell_frontier_push`` scatters one lane of every slot of a trip a
pass, so a trip costs its slots times the most lanes any ONE of them
holds; the frontier's columns are laid out by how many lanes hold them
first (the most first) and by id second, and a trip then holds one lane
count (two at a boundary between groups): no order runs fewer passes.
Held here, on frontiers built to cross every
seam of that layout, with the trips cut to 64 slots and 16 columns:

- the candidates are ``ell_frontier_sweep``'s on every row (none
  visited) and a numpy fold's, bit for bit, at widths 1, 4, 16 and 40
  (two membership words), on one tile and on four host devices as 2x2;
- the passes the walk reports are a numpy model's of that order
  (``conftest.walked_passes``), tile by tile, and on a level crafted so
  that the order by id alone ran two passes in every trip the new order
  runs one in all but the last."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu.parallel import ellmat
from combblas_tpu.parallel.grid import Grid

from conftest import walked_passes

N = 512
TRIP, COLUMNS = 64, 16  # the two trip sizes while a walk is traced
CAPACITY = 4096
GRIDS = {"1x1": (1, 1), "2x2": (2, 2)}
DEGREE = 8  # of every column but these: 8 columns fill a trip on 1x1
DEGREES = {0: 0, 5: 0, 300: 0, 7: 100, 301: 70}
SHAPES = [(1, "1x1"), (4, "1x1"), (16, "1x1"), (40, "1x1"),
          (4, "2x2"), (16, "2x2")]


def _graph():
    """Column ``c`` holds ``DEGREES.get(c, DEGREE)`` edges to rows drawn
    without repeat: directed, so a column's edges are its own."""
    rng = np.random.default_rng(53)
    deg = np.full(N, DEGREE)
    deg[list(DEGREES)] = list(DEGREES.values())
    cols = np.repeat(np.arange(N), deg)
    rows = np.concatenate([rng.choice(N, d, replace=False) for d in deg])
    order = np.lexsort((cols, rows))
    return rows[order].astype(np.int32), cols[order].astype(np.int32)


def _lanes(width, *lanes):
    """The lanes of ``lanes`` a width of ``width`` has (at least one)."""
    return sorted({min(l, width - 1) for l in lanes})


def _disjoint(width, front=None, first=16, each=10):
    """Lane ``l`` holds ``each`` columns no other lane holds."""
    front = np.zeros((N, width), bool) if front is None else front
    for l in range(width):
        front[first + l + width * np.arange(each) % (N - first), l] = True
    return front


def _held_by(k):
    def case(width):
        front = _disjoint(width, each=3)
        front[400, _lanes(width, *range(k))] = True
        return front
    return case


def _boundary(singles, pairs):
    """``pairs`` columns of two lanes and ``singles`` of one, their ids
    interleaved: on one tile the groups meet at slot ``8 * pairs``."""
    def case(width):
        front = np.zeros((N, width), bool)
        front[100 + 2 * np.arange(singles), 0] = True
        front[101 + 2 * np.arange(pairs)[:, None], _lanes(width, 0, 1)] = True
        return front
    return case


def _isolated_twice(width):
    """A root without an edge drawn twice, beside a shared hub."""
    front = _disjoint(width, each=2)
    for c in (0, 7, 300, 301):
        front[c, _lanes(width, 0, 1)] = True
    front[5, 0] = True
    return front


def _descending(width):
    """Low ids held by one lane, high ids by many and by half: a slot's
    place in ``rowidx`` and its column fall at each boundary."""
    front = np.zeros((N, width), bool)
    front[1 + np.arange(20), 0] = True
    front[[480, 490, 301], :] = True
    front[[470, 475], :max(width // 2, 1)] = True
    return front


def _every_trip_crossed(width):
    """Every eighth column (a trip's worth of slots on one tile) is held
    by two lanes, the seven between by one: by id, two passes a trip."""
    front = np.zeros((N, width), bool)
    ids = 16 + np.arange(8 * 12)
    front[ids, 0] = True
    front[ids[::8], _lanes(width, 1)] = True
    return front


def _random(width):
    return np.random.default_rng(width).random((N, width)) < 0.06


def _twins(width):
    """Every lane the same 30 columns: ``width`` passes a trip."""
    front = np.zeros((N, width), bool)
    front[np.random.default_rng(3).choice(N, 30, replace=False)] = True
    return front


CASES = {
    "no_shared_column": _disjoint,
    "one_column_in_2": _held_by(2),
    "one_column_in_3": _held_by(3),
    "one_column_in_all": _held_by(64),
    "boundary_inside_a_trip": _boundary(12, 6),
    "boundary_at_a_trip_s_edge": _boundary(16, 8),
    "isolated_root_twice_beside_a_shared_hub": _isolated_twice,
    "ids_fall_at_the_boundary": _descending,
    "every_trip_crossed": _every_trip_crossed,
    "random": _random,
    "twin_lanes": _twins,
}


@pytest.fixture(scope="module")
def walkers():
    """``get(width, grid)`` -> ``(E, rows, cols, trip, push, sweep)``,
    both jitted and traced here, with the trips cut (static: read when a
    walk is traced), on the empty frontier."""
    made = {}
    rows, cols = _graph()

    def get(width, grid):
        if (width, grid) not in made:
            g = Grid.make(*GRIDS[grid])
            E = ellmat.EllParMat.from_host_coo(
                g, rows, cols, np.ones(len(rows), np.float32), N, N)
            csc = ellmat.build_csc_companion(g, rows, cols, N, N)
            indptr, rowidx = ellmat.tile_lines(g, *csc)
            push = jax.jit(lambda m: ellmat.ell_frontier_push(
                E, indptr, rowidx, m, width, CAPACITY))
            sweep = jax.jit(lambda m: ellmat.ell_frontier_sweep(
                E, m, jnp.ones((g.pr, E.local_rows, width), jnp.bool_))[0])
            nothing = _member(E, np.zeros((N, width), bool))
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(ellmat, "PUSH_SLOT_CHUNK", TRIP)
                mp.setattr(ellmat, "PUSH_COLUMN_CHUNK", COLUMNS)
                y, passes = push(nothing)
            assert np.all(np.asarray(y) == -1) and not np.any(
                np.asarray(passes))
            trip = min(TRIP, CAPACITY, csc[1].shape[-1])
            made[width, grid] = E, rows, cols, trip, push, sweep
        return made[width, grid]

    return get


def _member(E, front):
    return ellmat.pack_lanes(jnp.asarray(front).reshape(
        E.grid.pc, E.local_cols, front.shape[1]))


def _fold(rows, cols, front):
    """``[N, W]``: the largest in-frontier column of each row, -1."""
    y = np.full(front.shape, -1, np.int32)
    for lane in range(front.shape[1]):
        keep = front[cols, lane]
        np.maximum.at(y[:, lane], rows[keep], cols[keep])
    return y


@pytest.mark.parametrize("width,grid", SHAPES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_walk_is_the_sweep_whatever_the_order_and_counts_its_passes(
        walkers, case, width, grid):
    E, rows, cols, trip, push, sweep = walkers(width, grid)
    front = CASES[case](width)
    member = _member(E, front)
    y, passes = push(member)
    assert y.shape == (E.grid.pr, E.local_rows, width)
    assert np.array_equal(np.asarray(y), np.asarray(sweep(member)))
    assert np.array_equal(np.asarray(y).reshape(N, width),
                          _fold(rows, cols, front))
    want = walked_passes(E, rows, cols, front, trip)
    assert np.array_equal(np.asarray(passes), want)
    if width <= ellmat.WORD_LANES:  # one word: no order runs fewer
        assert np.all(
            want <= walked_passes(E, rows, cols, front, trip, by_id=True))


@pytest.mark.parametrize("width", [4, 16])
def test_a_crossing_in_every_trip_costs_one_second_pass_not_one_a_trip(
        walkers, width):
    """On one tile, by hand: 96 columns of 8 edges are 12 trips of 64
    slots; one column in eight is in a second lane.  By id every trip
    holds such a column and runs two passes, 24; by lane count the
    twelve columns of two lanes fill the first trip and half the second,
    the 84 of one lane the rest: 2 + 2 + 10."""
    E, rows, cols, trip, push, _ = walkers(width, "1x1")
    front = _every_trip_crossed(width)
    assert trip == TRIP
    assert walked_passes(E, rows, cols, front, trip, by_id=True) == 24
    assert walked_passes(E, rows, cols, front, trip) == 14
    assert int(push(_member(E, front))[1][0, 0]) == 14
    # the groups meeting exactly at a trip's edge: 1 trip of two lanes,
    # 2 trips of one
    front = CASES["boundary_at_a_trip_s_edge"](width)
    assert int(push(_member(E, front))[1][0, 0]) == 2 + 2
    # 48 slots of two lanes, 96 of one: the first of three trips runs
    # two passes (five with the fewest lanes first: the two-lane slots
    # would end in the last, partly empty trip and begin in the second)
    front = CASES["boundary_inside_a_trip"](width)
    assert int(push(_member(E, front))[1][0, 0]) == 2 + 1 + 1
