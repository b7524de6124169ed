"""A deep graph through the served path (PR 52): BFS over a
high-diameter, bounded-degree graph, whose every level the device takes
as a walk of the frontier's own columns (``ellmat.ell_frontier_push``
inside the loop of ``models.bfs._bfs_batch_tallied``) where the class
sweep would gather the whole matrix.  Held to the benchmark's plain
reference (``chipbench.graph.Reference``: scipy hop counts, the Graph500
tree rules) and, bit for bit, to the all-pull program (the companion
withheld): parents, levels, ``niter``, through ``Server.submit``, at
widths 1, 4, 16, on one tile and on four host devices as 2x2."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chipbench import graph, rgggraph  # noqa: E402

from combblas_tpu import obs  # noqa: E402
from combblas_tpu.models import bfs as bfs_mod  # noqa: E402
from combblas_tpu.parallel.grid import Grid  # noqa: E402
from combblas_tpu.serve import GraphEngine, ServeConfig  # noqa: E402

from conftest import (  # noqa: E402
    push_capacity, push_trip, walked_edges, walked_passes)

GRIDS = {"1x1": (1, 1), "2x2": (2, 2)}


def _path(n=160):
    """A path of ``n`` vertices: 159 levels from an end, past the 126
    an int8 level holds (``bfs_batch_compact``'s cap, not the served
    path's)."""
    lo = np.arange(n - 1)
    keys = np.sort(np.concatenate([lo * n + lo + 1, (lo + 1) * n + lo]))
    return n, (keys // n).astype(np.int32), (keys % n).astype(np.int32)


def _rgg(n_log2=12, permuted=False):
    n, rows, cols, _ = rgggraph.rgg_graph(n_log2, 1)
    if not permuted:
        return n, rows, cols
    perm = np.random.default_rng(12).permutation(n)
    keys = np.sort(perm[rows].astype(np.int64) * n + perm[cols])
    return n, (keys // n).astype(np.int32), (keys % n).astype(np.int32)


GRAPHS = {"path": _path, "rgg": _rgg}


@pytest.fixture(scope="module")
def served():
    """``get(name, grid)`` -> ``(engine, reference)``: one engine (and so
    one set of traced plans) a graph and grid, for the module."""
    made = {}

    def get(name, grid):
        if (name, grid) not in made:
            n, rows, cols = GRAPHS[name]()
            made[name, grid] = (GraphEngine.from_coo(
                Grid.make(*GRIDS[grid]), rows, cols, n, kinds=("bfs",),
                keep_coo=True), graph.Reference(n, rows, cols))
        return made[name, grid]

    return get


def _fullest(eng, levels, niter):
    """The edges the fullest tile's frontier columns hold, level by
    level, for the batch whose answer is ``levels [n, W]``."""
    rows, cols, _ = eng.version.host_coo
    return [int(walked_edges(eng.E, rows, cols, levels == k).max())
            for k in range(niter)]


@jax.jit
def _all_pull(E, sources):
    return bfs_mod._bfs_batch_tallied(E, sources, None, True, None)


def _roots(ref, width, name):
    if name == "path":  # both ends and the middle: 159, 159, 80 levels
        return np.resize(
            np.asarray([0, ref.n - 1, ref.n // 2], np.int32), width)
    return graph.draw_roots(ref.deg, 52, width)


@pytest.mark.parametrize("name,grid,width", [
    pytest.param(
        name, grid, width, id=f"{name}-{grid}-{width}",
        # the mesh at the narrow widths adds seconds, not paths
        marks=[pytest.mark.slow] if grid == "2x2" and width < 16 else [])
    for name in sorted(GRAPHS) for grid in sorted(GRIDS)
    for width in (1, 4, 16)
])
def test_a_deep_search_is_the_references_and_the_all_pull_programs(
        served, name, grid, width):
    eng, ref = served(name, grid)
    roots = _roots(ref, width, name)
    srv = eng.serve(ServeConfig(lane_widths=(width,)))
    futures = [srv.submit("bfs", int(r)) for r in roots]
    srv.pump(force=True)
    answers = [f.result() for f in futures]
    want = _all_pull(eng.E, jnp.asarray(roots))
    parents = eng._lanes_to_global(np.asarray(want[0]))
    levels = eng._lanes_to_global(np.asarray(want[1]))
    depth = 0
    for lane, (root, got) in enumerate(zip(roots, answers)):
        assert ref.check_exact(got["levels"], int(root)) is None
        assert ref.check_tree(got["levels"], got["parents"], int(root)) is None
        # bit for bit the all-pull program's, every tie included
        np.testing.assert_array_equal(got["parents"], parents[:, lane])
        np.testing.assert_array_equal(got["levels"], levels[:, lane])
        assert got["batch_niter"] == int(want[2])
        # a vertex the root does not reach reads -1 in both arrays
        away = got["levels"] < 0
        assert np.all(got["parents"][away] == -1)
        depth = max(depth, int(got["levels"].max()))
    assert answers[0]["batch_niter"] == depth + 1
    if name == "path":
        assert depth == ref.n - 1 > 126
    # every level whose fullest tile fits was a walk, the others sweeps:
    # all of a lane's or four's, and of the path's
    *_, tally, push = eng.plan("bfs", width).fn(jnp.asarray(roots))
    fits = [e <= bfs_mod.push_capacity(eng.E)
            for e in _fullest(eng, levels, depth + 1)]
    assert int(push.levels) == sum(fits) > 0
    assert all(fits) or (name, width) == ("rgg", 16)
    swept = (depth + 1 - sum(fits)) * eng.grid.size * len(eng.E.buckets)
    assert int(np.sum(tally)) == swept


def test_a_relabelling_permutes_the_answer():
    """Nothing leans on the ids' locality: the rgg with its vertices
    relabelled by a random permutation gives the permuted hop counts, a
    tree the rules accept, and the all-pull program's parents."""
    n, rows, cols = _rgg()
    perm = np.random.default_rng(12).permutation(n)
    pn, prows, pcols = _rgg(permuted=True)
    ref, pref = graph.Reference(n, rows, cols), graph.Reference(pn, prows, pcols)
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), prows, pcols, pn, kinds=("bfs",), keep_coo=True)
    roots = graph.draw_roots(ref.deg, 7, 4)
    out = eng.execute("bfs", perm[roots].astype(np.int32))
    want = _all_pull(eng.E, jnp.asarray(perm[roots].astype(np.int32)))
    np.testing.assert_array_equal(
        out["parents"], eng._lanes_to_global(np.asarray(want[0])))
    for lane, root in enumerate(roots):
        levels = ref.bfs_levels(int(root))
        np.testing.assert_array_equal(out["levels"][perm, lane], levels)
        assert pref.check_tree(
            out["levels"][:, lane], out["parents"][:, lane],
            int(perm[root])) is None


@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_a_level_at_the_capacity_is_walked_and_one_edge_over_it_is_swept(
        served, grid):
    """The capacity is a count of edges a tile, held exactly: the widest
    level of a batch is walked by a program whose capacity is that
    level's fullest tile, and swept by one whose capacity is one edge
    less; same answers."""
    eng, ref = served("rgg", grid)
    roots = jnp.asarray(graph.draw_roots(ref.deg, 3, 4))
    want = _all_pull(eng.E, roots)
    levels = eng._lanes_to_global(np.asarray(want[1]))
    fullest = _fullest(eng, levels, int(want[2]))
    csc = eng._push_operand()

    def run(capacity):
        with push_capacity(capacity):  # static: a fresh trace reads it
            return jax.jit(lambda E, csc, s: bfs_mod._bfs_batch_tallied(
                E, s, None, True, csc))(eng.E, csc, roots)

    at, under = run(max(fullest)), run(max(fullest) - 1)
    swept = sum(e == max(fullest) for e in fullest)
    assert int(at[4].levels) == len(fullest)
    assert int(under[4].levels) == len(fullest) - swept
    classes = eng.grid.size * len(eng.E.buckets)
    assert int(np.sum(at[3])) == 0
    assert int(np.sum(under[3])) == swept * classes
    for got in (at, under):
        for a, b in zip(got[:3], want[:3]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_levels_and_edges_are_counted_where_the_sweeps_are(served):
    """Telemetry on: a batch's levels by mode add up to its ``niter``,
    the edges its pushes walked and the passes that scattered them are
    the host's count, and its ``execute`` stage record carries the four;
    telemetry off nothing of it is read
    (``test_obs.py::test_disabled_instrumentation_is_free``)."""
    eng, ref = served("rgg", "2x2")
    roots = graph.draw_roots(ref.deg, 5, 16)
    rows, cols, _ = eng.version.host_coo
    obs.disable()
    obs.reset()
    try:
        eng.execute("bfs", roots)
        assert obs.registry.snapshot() == []
        obs.enable(install_hooks=False)
        obs.trace.set_sample_rate(1.0)
        out = eng.execute("bfs", roots)
        niter = out["batch_niter"]
        walks = [walked_edges(eng.E, rows, cols, out["levels"] == k)
                 for k in range(niter)]
        capacity = bfs_mod.push_capacity(eng.E)
        fit = [k for k, w in enumerate(walks) if w.max() <= capacity]
        walks = [walks[k] for k in fit]
        walked = int(sum(walks).sum())
        trip = push_trip(eng.version.csc, capacity)
        passes = int(sum(walked_passes(
            eng.E, rows, cols, out["levels"] == k, trip).sum() for k in fit))
        assert passes >= len(walks)  # a walked level: a pass at least
        by = dict(width=16)
        get = obs.registry.get_counter
        pushed = len(walks)
        assert 0 < pushed < niter  # the widest levels are swept
        assert get("serve.bfs.levels", mode="push", **by) == pushed
        assert get("serve.bfs.levels", mode="pull", **by) == niter - pushed
        assert get("serve.bfs.push_edges", **by) == walked
        assert get("serve.bfs.push_passes", **by) == passes
        assert get("serve.bfs.push", outcome="taken") == 1
        srv = eng.serve(ServeConfig(lane_widths=(16,)))
        futures = [srv.submit("bfs", int(r)) for r in roots]
        srv.pump(force=True)
        assert all(f.result()["batch_niter"] == niter for f in futures)
        labels = [rec["labels"] for rec in obs.trace.records()]
        assert len(labels) == 16
        for lab in labels:
            assert [lab["levels"], lab["push_levels"], lab["push_edges"],
                    lab["push_passes"]] == [niter, pushed, walked, passes]
    finally:
        obs.disable()
        obs.reset()
        obs.trace.set_sample_rate(None)
