"""``models/matching.py:mcm_job``: upstream's maximum cardinality
matching as one library job over a ``BipartiteEll``, held to scipy's
cardinality, to ``chipbench/mcmref.py``'s validity check and, step for
step, to the numpy replay of ``tests/mcm_replay.py`` (which steps the
device walks, which it sweeps, and the edges its walks hold)."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(__file__))

from chipbench.mcmgraph import bipartite_rmat  # noqa: E402
from chipbench.mcmref import McmReference  # noqa: E402
from combblas_tpu import obs  # noqa: E402
from combblas_tpu.models import matching  # noqa: E402
from combblas_tpu.parallel import ellmat  # noqa: E402
from combblas_tpu.parallel.grid import Grid  # noqa: E402
from combblas_tpu.parallel.spmat import SpParMat  # noqa: E402
from conftest import push_capacity  # noqa: E402
from mcm_replay import replay  # noqa: E402

GRIDS = {"1x1": 1, "2x2": 2}


def _dedup(rows, cols, nc):
    keys = np.unique(np.asarray(rows, np.int64) * nc + cols)
    return (keys // nc).astype(np.int32), (keys % nc).astype(np.int32)


def _random(nr, nc, nnz, seed):
    rng = np.random.default_rng(seed)
    return _dedup(rng.integers(0, nr, nnz), rng.integers(0, nc, nnz), nc)


def _operand(p, rows, cols, nr, nc):
    return matching.BipartiteEll.from_host_coo(
        Grid.make(p, p), rows, cols, nr, nc)


def _program(M, capacity, init=None):
    """``_mcm_job_ell`` traced under a walk's ``capacity``: ``(mate_row,
    mate_col, counts)`` on the host."""
    with push_capacity(capacity):
        # (a function of its own: a trace is kept by the function)
        mr, mc, counts = jax.jit(
            lambda M, init: matching._mcm_job_ell.__wrapped__(M, init)
        )(M, init)
    nr, nc = M.A.nrows, M.A.ncols
    return (np.asarray(mr).reshape(-1)[:nr], np.asarray(mc).reshape(-1)[:nc],
            jax.device_get(counts))


def _held_to_reference(out, rows, cols, nr, nc):
    ref = McmReference(nr, nc, rows, cols)
    assert ref.check(out.mate_row, out.mate_col) is None
    assert out.cardinality == ref.cardinality >= out.init_matched
    return ref


@pytest.mark.parametrize("scale", [8, 9, 10])
def test_the_job_on_upstream_s_graph_class_reaches_scipy_s_maximum(scale):
    nr, nc, rows, cols = bipartite_rmat(scale, 16, 1)
    out = matching.mcm_job(_operand(1, rows, cols, nr, nc))
    ref = _held_to_reference(out, rows, cols, nr, nc)
    assert out.host_turns == 1
    assert out.init_matched <= ref.cardinality
    assert out.phases >= 1  # the one that augments nothing


@pytest.mark.parametrize("grid", GRIDS)
@pytest.mark.parametrize("nr,nc,nnz", [
    (300, 333, 1200), (257, 64, 700), (40, 500, 600)])
def test_rectangular_patterns_on_one_tile_and_on_a_mesh(grid, nr, nc, nnz):
    rows, cols = _random(nr, nc, nnz, seed=nr)
    out = matching.mcm_job(_operand(GRIDS[grid], rows, cols, nr, nc))
    _held_to_reference(out, rows, cols, nr, nc)


def test_empty_rows_and_columns_stay_unmatched():
    nr, nc = 200, 180
    rows, cols = _random(nr, nc, 500, seed=4)
    keep = (rows % 3 != 0) & (cols % 4 != 1)
    rows, cols = rows[keep], cols[keep]
    out = matching.mcm_job(_operand(2, rows, cols, nr, nc))
    _held_to_reference(out, rows, cols, nr, nc)
    assert (out.mate_row[::3] == -1).all()
    assert (out.mate_col[1::4] == -1).all()
    # no nonzero at all: nothing to match, one round, one phase
    none = np.zeros(0, np.int32)
    out = matching.mcm_job(_operand(1, none, none, 8, 8))
    assert out.cardinality == 0 and (out.mate_row == -1).all()
    assert (out.init_rounds, out.phases) == (2, 1)


@pytest.mark.parametrize("grid", GRIDS)
def test_a_perfect_matching_is_found(grid):
    n = 256
    rng = np.random.default_rng(9)
    perm = rng.permutation(n)
    rows, cols = _dedup(
        np.concatenate([np.arange(n), rng.integers(0, n, 3 * n)]),
        np.concatenate([perm, rng.integers(0, n, 3 * n)]), n)
    out = matching.mcm_job(_operand(GRIDS[grid], rows, cols, n, n))
    _held_to_reference(out, rows, cols, n, n)
    assert out.cardinality == n
    assert (np.sort(out.mate_row) == np.arange(n)).all()


def _blocks(grid, x, n, fill=-1):
    from combblas_tpu.parallel.vec import DistVec

    return DistVec.from_global(grid, x, align="row", fill=fill).blocks


@pytest.mark.parametrize("grid", GRIDS)
def test_one_long_path_is_chased_to_its_end(grid):
    """c0 - r0 - c1 - r1 - ... - ck - rk with every r_i matched to
    c_(i+1): the one augmenting path runs from rk back to c0 through
    every vertex, k + 1 layers deep, and the chase walks all of it."""
    k = 37
    p = GRIDS[grid]
    rows = np.concatenate([np.arange(k + 1), np.arange(k)]).astype(np.int32)
    cols = np.concatenate([np.arange(k + 1), np.arange(1, k + 1)]).astype(
        np.int32)
    rows, cols = _dedup(rows, cols, k + 1)
    M = _operand(p, rows, cols, k + 1, k + 1)
    g = M.A.grid
    mr0 = np.append(np.arange(1, k + 1), -1).astype(np.int32)
    mc0 = np.append(-1, np.arange(k)).astype(np.int32)
    mr, mc, counts = _program(
        M, 1 << 20, (_blocks(g, mr0, k + 1), _blocks(g, mc0, k + 1)))
    assert (mr == np.arange(k + 1)).all() and (mc == np.arange(k + 1)).all()
    assert int(counts["phases"]) == 2 and int(counts["augmented"]) == 1
    # k + 1 layers found the path, the second phase's one layer nothing
    assert int(counts["bfs"].steps.sum()) == k + 2
    # and from the empty matching Karp-Sipser alone solves a path
    out = matching.mcm_job(M)
    assert out.cardinality == k + 1 == out.init_matched and out.phases == 1


def test_a_maximum_matching_to_start_from_takes_the_one_phase_that_finds_nothing():
    nr, nc, rows, cols = bipartite_rmat(8, 16, 3)
    M = _operand(1, rows, cols, nr, nc)
    first = matching.mcm_job(M)
    g = M.A.grid
    mr, mc, counts = _program(M, 1 << 20, (
        _blocks(g, first.mate_row, nr), _blocks(g, first.mate_col, nc)))
    assert int(counts["phases"]) == 1 and int(counts["augmented"]) == 0
    assert (mr == first.mate_row).all() and (mc == first.mate_col).all()


@pytest.mark.parametrize("grid,capacity", [
    ("1x1", 300), ("1x1", 1 << 20), ("2x2", 100), ("2x2", 40)])
def test_rounds_layers_and_walked_edges_are_the_replay_s(grid, capacity):
    p = GRIDS[grid]
    nr, nc, rows, cols = bipartite_rmat(9, 16, 1)
    mr, mc, counts = _program(_operand(p, rows, cols, nr, nc), capacity)
    rp = replay(rows, cols, nr, nc, p, capacity)
    assert (mr == rp.mate_row).all() and (mc == rp.mate_col).all()
    assert int(counts["init_rounds"]) == rp.init_rounds
    assert int(counts["init_matched"]) == rp.init_matched
    assert int(counts["phases"]) == rp.phases
    for where in ("init", "bfs"):
        steps = [s for s in rp.steps if s.where == where]
        work = counts[where]
        assert work.steps.tolist() == [
            sum(s.push for s in steps), sum(not s.push for s in steps)]
        assert (work.edges == sum(
            s.edges for s in steps if s.push)).all()
        # a swept step sweeps every class of its matrix on every tile,
        # dense or skipped; a walked one none
        for way in ("A", "AT"):
            swept = sum(not s.push for s in steps if s.way == way)
            assert (work.sweeps[way].sum(axis=-1) == swept).all()
    if capacity < 1 << 20:
        assert any(not s.push for s in rp.steps)
        assert any(s.push for s in rp.steps)


@pytest.mark.parametrize("grid", GRIDS)
def test_more_candidates_than_lanes_are_chased_chunk_by_chunk(
        grid, monkeypatch):
    """From the empty matching with no round run, the first phase finds
    every column with a nonzero as a candidate: chased 4 lanes at a
    time, the mates are the replay's, which chases them all at once."""
    p = GRIDS[grid]
    nr, nc, rows, cols = bipartite_rmat(7, 8, 2)
    M = _operand(p, rows, cols, nr, nc)
    g = M.A.grid
    empty = (_blocks(g, np.full(nr, -1, np.int32), nr),
             _blocks(g, np.full(nc, -1, np.int32), nc))
    got = {}
    for lanes in (4, 1 << 14):
        monkeypatch.setattr(matching, "LIST_LANES", lanes)
        mr, mc, counts = _program(M, 1 << 20, empty)
        got[lanes] = (mr, mc, int(counts["phases"]), int(counts["augmented"]))
    for a, b in zip(got[4], got[1 << 14]):
        assert np.array_equal(a, b)
    ref = McmReference(nr, nc, rows, cols)
    assert ref.check(*got[4][:2]) is None
    assert got[4][3] == ref.cardinality > 4 * 8  # many chunks of 4
    # and the rounds' grants, settled 4 columns at a time
    monkeypatch.setattr(matching, "LIST_LANES", 4)
    mr, mc, counts = _program(M, 1 << 20)
    rp = replay(rows, cols, nr, nc, p, 1 << 20)
    assert (mr == rp.mate_row).all() and (mc == rp.mate_col).all()
    assert int(counts["init_rounds"]) == rp.init_rounds
    assert rp.init_matched > 4 * 8


def test_a_layer_at_the_capacity_is_walked_and_one_edge_over_it_swept():
    nr, nc, rows, cols = bipartite_rmat(9, 16, 1)
    M = _operand(1, rows, cols, nr, nc)
    free = replay(rows, cols, nr, nc, 1, 1 << 20)
    # the fullest layer of the phases: every other step holds less or
    # is one of the rounds' (whose fullest holds more)
    at = max(int(s.edges.sum()) for s in free.steps if s.where == "bfs")
    walked = {}
    for capacity in (at, at - 1):
        mr, mc, counts = _program(M, capacity)
        rp = replay(rows, cols, nr, nc, 1, capacity)
        assert (mr == rp.mate_row).all() and (mc == rp.mate_col).all()
        walked[capacity] = counts["bfs"].steps.tolist()
        layers = [s for s in rp.steps if s.where == "bfs"]
        assert walked[capacity] == [
            sum(s.push for s in layers), sum(not s.push for s in layers)]
    assert walked[at][1] == 0 and walked[at - 1][1] >= 1
    assert sum(walked[at]) == sum(walked[at - 1])


@pytest.mark.parametrize("grid", GRIDS)
def test_the_two_paths_and_the_host_oracle_agree_on_the_cardinality(grid):
    p = GRIDS[grid]
    nr, nc = 96, 80
    rows, cols = _random(nr, nc, 400, seed=11)
    ell = matching.mcm_job(_operand(p, rows, cols, nr, nc))
    A = SpParMat.from_global_coo(
        Grid.make(p, p), rows, cols, np.ones(len(rows), np.float32), nr, nc)
    coo = matching.mcm_job(A)
    assert coo.host_turns == coo.init_rounds + coo.phases > 1
    ref = _held_to_reference(coo, rows, cols, nr, nc)
    oracle = matching.maximum_matching(A, device=False)[1].to_global()
    assert ell.cardinality == coo.cardinality == int(
        (oracle >= 0).sum()) == ref.cardinality


@pytest.mark.parametrize("grid", GRIDS)
@pytest.mark.parametrize("width", [1, 3])
def test_the_walk_counts_where_the_served_bfs_takes_a_max(grid, width):
    """``ell_frontier_push(fold="count")``: for every (row, lane) how
    many of the row's in-neighbours the lane's frontier holds; the same
    walk as the max."""
    p = GRIDS[grid]
    g = Grid.make(p, p)
    nr, nc = 120, 90
    rows, cols = _random(nr, nc, 600, seed=2)
    E = ellmat.EllParMat.from_host_coo(
        g, rows, cols, np.ones(len(rows), np.float32), nr, nc)
    lines = ellmat.tile_lines(g, *ellmat.build_csc_companion(
        g, rows, cols, nr, nc))
    frontier = np.random.default_rng(3).random((nc, width)) < 0.3
    lc = g.local_cols(nc)
    padded = np.zeros((p * lc, width), bool)
    padded[:nc] = frontier
    member = ellmat.pack_lanes(jnp.asarray(padded.reshape(p, lc, width)))
    out = {}
    for fold in ("count", "max"):
        y, _ = jax.jit(lambda m, fold=fold: ellmat.ell_frontier_push(
            E, *lines, m, width, 1 << 12, fold))(member)
        out[fold] = np.asarray(y).reshape(-1, width)[:nr]
    dense = np.zeros((nr, nc), np.int64)
    dense[rows, cols] = 1
    assert (out["count"] == dense @ frontier).all()
    ids = np.where(frontier, np.arange(nc)[:, None], -1)
    want = np.where(dense[:, :, None] == 1, ids[None], -1).max(axis=1)
    assert (out["max"] == want).all()


def _counters():
    return {
        (r["name"], r.get("labels", {}).get("mode")): r["value"]
        for r in obs.registry.snapshot()
        if r["name"].startswith("models.mcm.") and r["kind"] == "counter"}


def test_counters_add_a_job_s_own_counts_once_and_nothing_when_off():
    nr, nc, rows, cols = bipartite_rmat(8, 16, 1)
    M = _operand(1, rows, cols, nr, nc)
    obs.reset()
    out = matching.mcm_job(M)
    assert obs.registry.snapshot() == [] and obs.spans() == []
    counts = jax.device_get(matching._mcm_job_ell(M)[2])
    obs.enable(install_hooks=False)
    try:
        matching.mcm_job(M)
        got = _counters()
        assert got["models.mcm.jobs", None] == 1
        assert got["models.mcm.init_rounds", None] == out.init_rounds
        assert got["models.mcm.init_matched", None] == out.init_matched
        assert got["models.mcm.phases", None] == out.phases
        assert got["models.mcm.augmented", None] == (
            out.cardinality - out.init_matched)
        assert got["models.mcm.host_turns", None] == 1
        for name, key in (("init_steps", "init"), ("layers", "bfs")):
            assert [got[f"models.mcm.{name}", m] for m in
                    matching.LAYER_MODES] == counts[key].steps.tolist()
        # two steps a round, and the walked edges of both loops
        assert sum(counts["init"].steps) == 2 * out.init_rounds
        assert got["models.mcm.push_edges", None] == int(
            counts["init"].edges.max() + counts["bfs"].edges.max())
        # the job is one batch of the ELL family under its own kind
        assert [r["value"] for r in obs.registry.snapshot()
                if r["name"] == "ell.batches"
                and r["labels"].get("kind") == "mcm"] == [1]
        # the first traced call published the program's op names
        names = set(obs.opnames.tables()["jit__mcm_job_ell"].values())
        for scope in ("mcm.init", "mcm.phase", "mcm.bfs", "mcm.chase",
                      "mcm.augment"):
            assert any(f"/{scope}/" in nm for nm in names), scope
        # an SpParMat job counts its turns and no ELL work
        A = SpParMat.from_global_coo(
            Grid.make(1, 1), rows, cols, np.ones(len(rows), np.float32),
            nr, nc)
        coo = matching.mcm_job(A)
        got = _counters()
        assert got["models.mcm.jobs", None] == 2
        assert got["models.mcm.host_turns", None] == 1 + coo.host_turns
    finally:
        obs.disable()
        obs.reset()


def test_the_lowered_program_holds_every_scope_and_both_ways_of_a_step():
    nr, nc, rows, cols = bipartite_rmat(8, 16, 1)
    text = matching._mcm_job_ell.lower(
        _operand(1, rows, cols, nr, nc)).as_text(debug_info=True)
    for scope in matching.MCM_SCOPES:
        assert f"{scope}/" in text or f"{scope}\"" in text, scope
