"""FastSV on an ``EllParMat`` (``models/cc.py``: the argument's type
picks the sweep): against scipy's components labelled with their
smallest vertex id, bit for bit against the ``SpParMat`` entry, on every
shape a whole-graph job meets; the wrapper's counters; the program's
scopes."""

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse import csgraph

from combblas_tpu import obs
from combblas_tpu.models import cc
from combblas_tpu.parallel.ellmat import EllParMat
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat


def _symmetric(n, pairs):
    a = np.array([p[0] for p in pairs], np.int64)
    b = np.array([p[1] for p in pairs], np.int64)
    return n, np.concatenate([a, b]), np.concatenate([b, a])


def _rmat9():
    """Isolated vertices and one giant component."""
    from chipbench import graph

    n, rows, cols, _ = graph.rmat_graph(9, 16, 1)
    return n, rows, cols


def _cliques_and_paths():
    """40 disjoint pieces of 3 to 10 vertices, cliques and paths by
    turns, numbered so that no piece's vertices are consecutive."""
    sizes = [3 + k % 8 for k in range(40)]
    n = sum(sizes)
    ids = np.random.default_rng(5).permutation(n)
    pairs, at = [], 0
    for k, size in enumerate(sizes):
        piece = ids[at:at + size]
        at += size
        if k % 2:
            pairs += list(zip(piece, piece[1:]))
        else:
            pairs += [(u, v) for i, u in enumerate(piece)
                      for v in piece[i + 1:]]
    return _symmetric(n, pairs)


def _path300():
    """Many rounds and jumps: ids along the path are shuffled, so labels
    travel far."""
    ids = np.random.default_rng(6).permutation(300)
    return _symmetric(300, list(zip(ids, ids[1:])))


def _empty():
    return 64, np.empty(0, np.int64), np.empty(0, np.int64)


GRAPHS = {"rmat9": _rmat9, "cliques_and_paths": _cliques_and_paths,
          "path300": _path300, "empty": _empty}


def _want(n, rows, cols):
    adj = sp.csr_matrix((np.ones(len(rows)), (rows, cols)), shape=(n, n))
    count, comp = csgraph.connected_components(adj, directed=False)
    smallest = np.full(count, n)
    np.minimum.at(smallest, comp, np.arange(n))
    return smallest[comp]


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
@pytest.mark.parametrize("name", list(GRAPHS))
def test_fastsv_on_ell_is_scipy_s_components_and_the_coo_entry_s(name, shape):
    import jax

    if len(jax.devices()) < shape[0] * shape[1]:
        pytest.skip("the 2 x 2 grid needs four devices")
    n, rows, cols = GRAPHS[name]()
    grid = Grid.make(*shape)
    ones = np.ones(len(rows), np.float32)
    E = EllParMat.from_host_coo(grid, rows, cols, ones, n, n)
    labels, rounds, jumps = cc.fastsv(E)
    got = labels.to_global()
    assert got.dtype == np.int32 and got.shape == (n,)
    assert np.array_equal(got, _want(n, rows, cols))
    # an isolated vertex labels itself
    lonely = np.setdiff1d(np.arange(n), rows)
    assert np.array_equal(got[lonely], lonely)
    assert int(rounds) >= 1 and int(jumps) >= 1
    if name == "path300":
        assert int(rounds) > 3
    if name == "empty":
        assert (int(rounds), int(jumps)) == (1, 1)
    # the COO entry runs the same loop over another sweep
    A = SpParMat.from_global_coo(grid, rows, cols, ones, n, n)
    coo, coo_rounds, coo_jumps = cc.fastsv(A)
    assert np.array_equal(coo.to_global(), got)
    assert (int(coo_rounds), int(coo_jumps)) == (int(rounds), int(jumps))
    # and the two-value entry its callers know is the same answer
    two = cc.connected_components(E)
    assert len(two) == 2 and int(two[1]) == int(rounds)
    assert np.array_equal(two[0].to_global(), got)


def test_a_warm_start_from_same_component_labels_ends_where_iota_does():
    from combblas_tpu.parallel.vec import DistVec

    n, rows, cols = _cliques_and_paths()
    grid = Grid.make(1, 1)
    E = EllParMat.from_host_coo(
        grid, rows, cols, np.ones(len(rows), np.float32), n, n)
    cold, rounds, _ = cc.fastsv(E)
    f0 = DistVec.from_global(grid, cold.to_global(), align="row")
    warm, warm_rounds, _ = cc.fastsv(E, f0)
    assert np.array_equal(warm.to_global(), cold.to_global())
    assert int(warm_rounds) == 1 <= int(rounds)


def _counters():
    return {rec["name"]: rec["value"] for rec in obs.registry.snapshot()
            if rec["name"].startswith("models.cc.")}


def _ell_family():
    """The ELL family's series of kind "cc", by name and mode, the
    degree classes added up."""
    out = {}
    for rec in obs.registry.snapshot():
        if rec["name"].startswith("ell."):
            assert (rec["labels"]["kind"], rec["labels"]["width"]) == ("cc", 1)
            key = rec["name"], rec["labels"].get("mode")
            out[key] = out.get(key, 0) + rec["value"]
    return out


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_counters_add_a_job_s_own_counts_once_and_nothing_when_off(shape):
    from combblas_tpu.parallel.ellmat import class_slots

    n, rows, cols = _path300()
    E = EllParMat.from_host_coo(
        Grid.make(*shape), rows, cols, np.ones(len(rows), np.float32), n, n)
    tiles, slots = shape[0] * shape[1], class_slots(E)
    obs.reset()
    _, rounds, jumps = cc.fastsv(E)
    assert obs.registry.snapshot() == [] and obs.spans() == []
    # the sweeps a job ran come from the program (its fourth output):
    # the rounds whose grandparents were not the round before's
    sweeps = int(cc.cc_fastsv_ell(E, None)[3])
    assert 1 <= sweeps <= int(rounds)
    obs.enable(install_hooks=False)
    try:
        cc.fastsv(E)
        assert _counters() == {
            "models.cc.jobs": 1, "models.cc.rounds": int(rounds),
            "models.cc.jumps": int(jumps)}
        # the one-lane sweep has no choice in it: every sweep gathers
        # every class of every tile, and a tile's slots are the job's
        assert _ell_family() == {
            ("ell.batches", None): 1,
            ("ell.class_sweeps", "dense"): sweeps * len(slots) * tiles,
            ("ell.class_sweeps", "skipped"): 0,
            ("ell.slots", "dense"): sweeps * sum(slots),
            ("ell.slots", "skipped"): 0}
        # an SpParMat's sweep has no classes: a job, and no ELL work
        A = SpParMat.from_global_coo(
            Grid.make(*shape), rows, cols, np.ones(len(rows), np.float32),
            n, n)
        cc.fastsv(A)
        assert _counters()["models.cc.jobs"] == 2
        assert _ell_family()["ell.batches", None] == 1
        cc.connected_components(E)
        assert _counters()["models.cc.jobs"] == 3
        assert _counters()["models.cc.rounds"] == 3 * int(rounds)
        assert _ell_family()["ell.slots", "dense"] == 2 * sweeps * sum(slots)
        # the first traced call published the program's op names
        assert any("cc.iter" in nm for nm in obs.opnames.tables()[
            "jit_cc_fastsv_ell"].values())
    finally:
        obs.disable()
        obs.reset()


@pytest.mark.parametrize("program,kind", [
    (cc.cc_fastsv_ell, "ell"), (cc.cc_fastsv, "coo")])
def test_the_lowered_program_holds_every_scope(program, kind):
    n, rows, cols = _rmat9()
    grid = Grid.make(1, 1)
    ones = np.ones(len(rows), np.float32)
    M = (EllParMat.from_host_coo(grid, rows, cols, ones, n, n)
         if kind == "ell"
         else SpParMat.from_global_coo(grid, rows, cols, ones, n, n))
    text = program.lower(M, None).as_text(debug_info=True)
    assert [s for s in cc.CC_SCOPES if s not in text] == []
    # the one-lane sweep reads by class and phase like its multi-lane twin
    for leaf in ("gather", "fold", "scatter_rows"):
        assert (f"ell.bucket0/{leaf}" in text) == (kind == "ell")
    assert "jit_cc_fastsv" in text
    assert ("jit_cc_fastsv_ell" in text) == (kind == "ell")
