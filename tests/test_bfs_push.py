"""Level 0 of the served BFS plan as a push over the roots' columns
(``models.bfs._bfs_batch_tallied(csc=...)``, ``ellmat.ell_frontier_push``):
the answer is the all-pull program's bit for bit, whatever the device
chose, and the CSC companion it walks lives with the graph version
(built by ``from_coo``, carried by snapshots and merges)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from combblas_tpu.models import PAD_ROOT
from combblas_tpu.models import bfs as bfs_mod
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import GraphEngine

SCALE = 10
N = 1 << SCALE
GRIDS = {"1x1": (1, 1), "2x2": (2, 2)}
TAKEN, OVER_BUDGET, STALE = range(3)


def _rmat(directed: bool):
    """Graph500 R-MAT at ``SCALE``; ``directed``: the upper triangle's
    edges only, so every column's out-edges differ from its in-edges.
    Vertices N-3.. are cut off (isolated roots)."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from chipbench import graph

    n, rows, cols, _ = graph.rmat_graph(SCALE, 16, 1)
    keep = (rows < N - 3) & (cols < N - 3)
    if directed:
        keep &= rows < cols
    return rows[keep], cols[keep]


@pytest.fixture(scope="module")
def engines():
    """``get(grid, directed=False, capacity=None)``: one engine (and so
    one set of traced plans) per distinct request, for the module."""
    made = {}

    def get(grid, directed=False, capacity=None):
        key = (grid, directed, capacity)
        if key not in made:
            rows, cols = _rmat(directed)
            made[key] = (GraphEngine.from_coo(
                Grid.make(*GRIDS[grid]), rows, cols, N, kinds=("bfs",),
                symmetric=not directed, keep_coo=True, headroom=0.5,
            ), rows, cols)
        return made[key]

    return get


@jax.jit
def _all_pull_program(E, sources):
    return bfs_mod._bfs_batch_tallied(
        E, sources, None, True, None)


def _all_pull(E, sources):
    """The served program with no companion handed to it: level 0 in
    the loop, as before there was a push."""
    return _all_pull_program(E, jnp.asarray(sources))


def _same_answer(got, want):
    for name, a, b in zip(("parents", "levels", "niter"), got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def _roots(case, width, rows, cols):
    deg = np.bincount(cols, minlength=N)
    live = np.nonzero(deg)[0]
    rng = np.random.default_rng([width, len(case)])
    src = rng.choice(live, width, replace=False).astype(np.int32)
    if case == "pad_lanes":
        src[width // 2:] = PAD_ROOT  # width 1: the whole batch is pad
    elif case == "twin_lanes" and width > 1:
        src[-1] = src[0]
    elif case == "isolated_root":
        src[0] = N - 1
        assert deg[N - 1] == 0 and not np.any(rows == N - 1)
    elif case == "over_budget":
        src[0] = int(np.argmax(deg))  # the hub alone passes 8 edges
    return src


@pytest.mark.parametrize("width", [1, 4, 16])
@pytest.mark.parametrize("grid", sorted(GRIDS))
@pytest.mark.parametrize("case", [
    "fresh_roots", "pad_lanes", "twin_lanes", "isolated_root",
    "directed", "over_budget", "stale_companion",
])
def test_push_first_is_the_all_pull_answer(
        engines, monkeypatch, case, grid, width):
    capacity = 8 if case == "over_budget" else None
    if capacity is not None:
        # static: read when the plan is traced, on this engine's first use
        monkeypatch.setattr(bfs_mod, "PUSH_EDGE_CAPACITY", capacity)
    eng, rows, cols = engines(grid, case == "directed", capacity)
    src = _roots(case, width, rows, cols)
    if case != "stale_companion":
        *got, tally, push = eng.plan("bfs", width).fn(jnp.asarray(src))
        want = _all_pull(eng.E, src)
        _same_answer(got, want)
        assert want[4] is None  # a program without a push reports nothing
        assert int(push.outcome) == (
            OVER_BUDGET if case == "over_budget" else TAKEN)
        # a walk makes a pass at least; no walk, none
        assert push.passes.shape == (eng.grid.pr, eng.grid.pc)
        assert bool(np.any(np.asarray(push.passes))) == bool(
            np.any(np.asarray(push.edges)))
        # a level taken as a push sweeps no degree class
        tiles, classes = eng.grid.size, len(eng.E.buckets)
        assert int(push.levels) >= (int(push.outcome) == TAKEN)
        pushed_levels = tiles * classes * int(push.levels)
        assert int(np.sum(tally)) + pushed_levels == int(np.sum(want[3]))
        return

    # a structural merge: the parent's companion rides along for its
    # shapes, marked not-current; the swap retraces nothing, the device
    # reads the mark and runs level 0 in the loop
    from combblas_tpu.dynamic import DeltaBatch

    eng.plan("bfs", width).fn(jnp.asarray(src))  # traced before the swap
    parent = eng.version
    assert parent.csc_current
    root = int(src[0])
    nbr = int(rows[cols == root][0])
    mark = eng.trace_mark()
    child = eng.apply_delta(DeltaBatch.from_ops(
        [("delete", root, nbr), ("delete", nbr, root)]))
    assert child.dyn.last_stats.mode == "incremental"
    assert child.csc is parent.csc and not child.csc_current
    eng.swap(child)
    try:
        *got, tally, push = eng.plan("bfs", width).fn(jnp.asarray(src))
        want = _all_pull(eng.E, src)
        _same_answer(got, want)
        # a stale companion pushes nothing, at any level
        assert int(push.outcome) == STALE and int(push.levels) == 0
        assert not np.any(np.asarray(push.edges))
        assert not np.any(np.asarray(push.passes))
        assert np.array_equal(np.asarray(tally), np.asarray(want[3]))
        # the edge is gone from the answer, though the companion has it
        lane0 = np.asarray(got[0])[..., 0].reshape(-1)
        assert lane0[nbr] != root
        # rebuilt off the query path, at the length the plans know
        eng.csc_companion()
        assert eng.version.csc_current
        assert eng.version.csc[1].shape == parent.csc[1].shape
        *got, _, push = eng.plan("bfs", width).fn(jnp.asarray(src))
        _same_answer(got, want)
        assert int(push.outcome) == TAKEN
        assert eng.retraces_since(mark) == 0
    finally:
        eng.swap(parent)  # the module's engine, as the other cases know it


def test_library_callers_keep_the_all_pull_program(engines):
    """``bfs_batch`` holds no companion: its program has no push in it
    (what every library caller got before), and the served plan, which
    has, gives the same answer."""
    eng, rows, cols = engines("2x2")
    src = _roots("fresh_roots", 4, rows, cols)
    plain = bfs_mod.bfs_batch(eng.E, jnp.asarray(src))
    served = eng.execute("bfs", src)
    assert np.array_equal(plain[0].to_global(), served["parents"])
    assert np.array_equal(plain[1].to_global(), served["levels"])
    assert int(plain[2]) == served["batch_niter"]
    lowered = bfs_mod._bfs_batch_impl.lower(eng.E, jnp.asarray(src))
    assert "bfs.push" not in lowered.as_text(debug_info=True)
    assert "bfs.push" in eng.plan("bfs", 4).lower(
        jnp.asarray(src)).as_text(debug_info=True)


def test_counter_reads_what_the_device_chose(engines):
    from combblas_tpu import obs

    eng, rows, cols = engines("1x1")
    src = _roots("fresh_roots", 4, rows, cols)

    def taken():
        return sum(
            r["value"] for r in obs.registry.snapshot()
            if r["name"] == "serve.bfs.push"
            and r["labels"] == {"outcome": "taken"})

    was = obs.ENABLED
    obs.enable()
    try:
        before = taken()
        out = eng.execute("bfs", src)
        assert taken() == before + 1
    finally:
        if not was:
            obs.disable()
    assert np.array_equal(out["levels"][src, np.arange(4)], np.zeros(4))


# --- the operand's life ------------------------------------------------------


@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_snapshot_carries_the_companion(engines, tmp_path, grid):
    from combblas_tpu.utils import checkpoint

    eng, rows, cols = engines(grid)
    v = eng.version
    path = str(tmp_path / "v.npz")
    checkpoint.save_version(path, v)
    back = checkpoint.load_version(path, eng.grid)
    assert back.csc_current
    for a, b in zip(v.csc, back.csc):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert a.sharding == b.sharding
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the version's device bytes hold it
    without = back.device_bytes() - sum(int(a.nbytes) for a in back.csc)
    back.csc = None
    assert back.device_bytes() == without
    # a version marked not-current says so after the round trip
    v.csc_current = False
    try:
        checkpoint.save_version(path, v)
    finally:
        v.csc_current = True
    assert not checkpoint.load_version(path, eng.grid).csc_current


def test_snapshot_from_before_the_companion_serves_stale(
        engines, tmp_path):
    """A snapshot written before PR 29 has no companion: it loads, the
    engine hands the plan a stand-in marked not-current, and every batch
    runs level 0 in the loop (outcome ``stale``, same answer)."""
    from combblas_tpu.utils import checkpoint

    eng, rows, cols = engines("2x2")
    old = eng.version
    csc, coo = old.csc, old.host_coo
    old.csc, old.host_coo = None, None  # as PR 28 saved it, no keep_coo
    path = str(tmp_path / "old.npz")
    try:
        checkpoint.save_version(path, old)
    finally:
        old.csc, old.host_coo = csc, coo
    with np.load(path) as z:
        assert "csc.indptr" not in z
    back = checkpoint.load_version(path, eng.grid)
    assert back.csc is None
    served = GraphEngine(eng.grid, version=back, kinds=("bfs",))
    served.warmup(widths=(4,))  # no host COO: nothing to rebuild from
    src = _roots("fresh_roots", 4, rows, cols)
    *got, _, push = served.plan("bfs", 4).fn(jnp.asarray(src))
    _same_answer(got, _all_pull(eng.E, src))
    assert int(push.outcome) == STALE and int(push.levels) == 0
    assert not np.any(np.asarray(push.passes))
    assert not served.version.csc_current
    with pytest.raises(ValueError, match="keep_coo"):
        served.csc_companion()


def test_write_lane_rebuilds_a_stale_companion_when_quiet():
    """A structural merge marks the companion not-current; the write
    lane, its buffer empty, rebuilds it at the length the plans were
    traced with: reads after it take the push again, zero retraces."""
    import time

    from combblas_tpu.serve import ServeConfig

    rows, cols = _rmat(False)
    eng = GraphEngine.from_coo(
        Grid.make(2, 2), rows, cols, N, kinds=("bfs",), keep_coo=True)
    present = set(zip(rows.tolist(), cols.tolist()))
    a, b = next((a, b) for a in range(N) for b in range(a + 1, N)
                if (a, b) not in present)
    cfg = ServeConfig(lane_widths=(1,), max_wait_s=0.005,
                      update_flush=2, update_max_delay_s=0.01)
    with eng.serve(cfg) as srv:
        srv.warmup()
        mark = eng.trace_mark()
        shape = eng.version.csc[1].shape
        res = srv.submit_update(
            [("insert", a, b), ("insert", b, a)]).result(timeout=60)
        assert res["mode"] == "incremental"
        deadline = time.monotonic() + 60
        while not eng.version.csc_current and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.version.csc_current
        assert eng.version.csc[1].shape == shape
        out = srv.submit("bfs", a).result(timeout=60)
        assert out["levels"][b] == 1 and out["parents"][b] == a
        *_, push = eng.plan("bfs", 1).fn(jnp.asarray([a], jnp.int32))
        assert int(push.outcome) == TAKEN
        assert eng.retraces_since(mark) == 0
