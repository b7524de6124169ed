"""The work of the ELL class loop as a served batch records it (PR 50):
the plan returns how often each tile swept or skipped each degree class,
``GraphEngine._collect`` weighs that by the ``class_slots`` of the version
the batch was launched on into the one counter family
(``ell.class_sweeps`` / ``ell.slots`` / ``ell.batches``), and the batch's
``execute`` stage record carries ``slots`` and ``slots_skipped``, and,
since PR 52, ``levels``, ``push_levels``, ``push_edges`` and (PR 53)
``push_passes``: the levels the device walked instead of sweeping
(``serve.bfs.levels{mode}``, ``serve.bfs.push_edges``,
``serve.bfs.push_passes``).  Held on one small directed graph
to a numpy replay of the levels' masks (``test_bfs_bits.py``'s), on one
tile and on a 2x2 grid, whose busiest tile the slots are; kernel 3's and
BC's loops are held to theirs in ``test_sssp_floor_mask.py`` and
``test_bc_served.py``, FastSV's in ``test_cc_ell.py``."""

import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu import obs
from combblas_tpu.models.bfs import PUSH_OUTCOMES
from combblas_tpu.parallel.ellmat import (
    SWEEP_MODES, class_slots, count_sweep_work)
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import GraphEngine, ServeConfig

from conftest import (
    counter_sum, push_capacity, push_trip, pushed_levels, walked_passes)
from test_bfs_bits import (
    CAPACITY, _graph, _numpy_bfs, _numpy_tally, _roots)

WIDTH = 4
GRIDS = [(1, 1), (2, 2)]


@pytest.fixture(scope="module", params=GRIDS, ids=lambda g: f"{g[0]}x{g[1]}")
def engine(request):
    rows, cols, n = _graph("ragged")
    eng = GraphEngine.from_coo(
        Grid.make(*request.param), rows, cols, n, kinds=("bfs",),
        symmetric=False)
    with push_capacity(CAPACITY):  # static: read when the plan is traced
        eng.warmup(kinds=("bfs",), widths=(WIDTH,))
    return eng


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()
    obs.trace.set_sample_rate(None)


def _walks(engine, roots, graph=None, capacity=CAPACITY):
    """``(history, walks)`` of a numpy BFS of ``roots``: every level of
    the batch's loop, and the edges by tile of those the device walks
    (None: a level it sweeps; ``conftest.pushed_levels``)."""
    rows, cols, n = graph or _graph("ragged")
    history = _numpy_bfs(rows, cols, n, roots)[3]
    return history, pushed_levels(engine.E, rows, cols, history, capacity)


def _passes(engine, roots, graph=None, capacity=CAPACITY):
    """The scatter passes of the levels of ``_walks`` the device walks,
    all tiles (``conftest.walked_passes``)."""
    rows, cols, n = graph or _graph("ragged")
    history, walks = _walks(engine, roots, graph, capacity)
    trip = push_trip(engine.version.csc, capacity)
    return int(sum(
        walked_passes(engine.E, rows, cols, frontier, trip).sum()
        for (frontier, _), w in zip(history, walks) if w is not None))


def _replay(engine, roots, graph=None, capacity=CAPACITY):
    """The per-tile, per-class tally a numpy BFS of ``roots`` finds for
    the batch's loop: the levels that are swept, not walked."""
    history, walks = _walks(engine, roots, graph, capacity)
    return np.asarray(_numpy_tally(
        engine.E, [h for h, w in zip(history, walks) if w is None]))


def _busiest(tally, slots):
    """``[dense, skipped]`` slots of the tile that gathered most."""
    by_tile = (tally * np.asarray(slots)[:, None]).sum(axis=2).reshape(-1, 2)
    return by_tile[np.argmax(by_tile[:, 0])]


def test_the_plan_s_tally_is_the_numpy_replay_class_by_class(engine):
    roots = _roots("tie", "ragged", WIDTH)
    plan = engine.plan("bfs", WIDTH)
    *_, niter, tally, push = plan.fn(jnp.asarray(roots))
    tally = np.asarray(tally)
    g = engine.grid
    assert tally.shape == (g.pr, g.pc, len(engine.E.buckets), 2)
    assert np.array_equal(tally, _replay(engine, roots))
    # a choice a swept level, tile and class (the others were walked);
    # the weights are the served version's
    assert PUSH_OUTCOMES[int(push.outcome)] == "taken"
    assert 0 < int(push.levels) < int(niter)
    assert (tally.sum(axis=-1) == int(niter) - int(push.levels)).all()
    assert engine._swept("bfs") == (({}, class_slots(engine.E)),)
    assert class_slots(engine.E) == tuple(
        bc.shape[2] * bc.shape[3] for bc, _, _ in engine.E.buckets)
    assert tally[..., 1].any() and tally[..., 0].any()


def test_a_batch_adds_its_counts_times_its_version_s_slots(engine):
    roots = _roots("tie", "ragged", WIDTH)
    want = _replay(engine, roots)
    obs.reset()
    engine.execute("bfs", roots)  # telemetry off: nothing read, no series
    assert obs.registry.snapshot() == [] and not obs.trace.records()
    obs.enable(install_hooks=False)
    out = engine.execute("bfs", roots)
    engine.execute("bfs", roots)
    by = dict(kind="bfs", width=WIDTH)
    assert obs.registry.get_counter("ell.batches", **by) == 2
    slots = class_slots(engine.E)
    walks = [w for w in _walks(engine, roots)[1] if w is not None]
    iters = out["batch_niter"] - len(walks)  # the swept levels
    # levels by how each was run, and the edges the walked ones held
    for mode, ran in (("push", len(walks)), ("pull", iters)):
        assert obs.registry.get_counter(
            "serve.bfs.levels", mode=mode, width=WIDTH) == 2 * ran
    assert obs.registry.get_counter(
        "serve.bfs.push_edges", width=WIDTH) == 2 * int(sum(walks).sum())
    assert obs.registry.get_counter(
        "serve.bfs.push_passes", width=WIDTH) == 2 * _passes(engine, roots)
    for m, mode in enumerate(SWEEP_MODES):
        assert counter_sum("ell.class_sweeps", mode=mode, **by) == (
            2 * want[..., m].sum())
        assert counter_sum("ell.slots", mode=mode, **by) == (
            2 * _busiest(want, slots)[m])
        # class by class: the busiest tile's counts times the class's slots
        tile = np.argmax((want[..., 0] * np.asarray(slots)).sum(axis=2))
        mine = want.reshape(-1, len(slots), 2)[tile]
        for cls, size in enumerate(slots):
            assert obs.registry.get_counter(
                "ell.slots", cls=cls, mode=mode, **by) == (
                    2 * size * mine[cls, m])
    # dense + skipped = iterations x classes (x tiles), and by slots
    assert counter_sum("ell.class_sweeps", **by) == (
        2 * iters * len(slots) * engine.grid.size)
    assert counter_sum("ell.slots", **by) == 2 * iters * sum(slots)
    assert obs.registry.get_counter("serve.bfs.push", outcome="taken") == 2


def test_the_stage_records_carry_their_batch_s_work(engine):
    rows, cols, n = _graph("ragged")
    obs.enable(install_hooks=False)
    obs.trace.set_sample_rate(1.0)
    srv = engine.serve(ServeConfig(lane_widths=(WIDTH,)))
    roots = [int(r) for r in _roots("spread", "ragged", 3 * WIDTH - 1)]
    for at in range(0, len(roots), WIDTH):  # the last batch a lane short
        futures = [srv.submit("bfs", r) for r in roots[at:at + WIDTH]]
        srv.pump(force=True)
        assert all("parents" in f.result() for f in futures)
    records = obs.trace.records()
    assert len(records) == len(roots)
    slots = class_slots(engine.E)
    seen = {}
    for rec in records:
        lab = rec["labels"]
        assert lab["width"] == WIDTH and lab["plan"] == "warm"
        assert "class_sweeps" not in lab
        assert (lab["slots"] + lab["slots_skipped"]) % sum(slots) == 0
        assert 0 < lab["push_levels"] < lab["levels"]
        execute = [s for s in rec["stages"] if s["stage"] == "execute"][0]
        assert [p["stage"] for p in execute["parts"]][:2] == [
            "launch", "device"]
        seen[execute["s"]] = lab  # one pair of marks a batch
    assert len(seen) == 3
    by = dict(kind="bfs", width=WIDTH)
    assert obs.registry.get_counter("ell.batches", **by) == 3
    for attr, mode in (("slots", "dense"), ("slots_skipped", "skipped")):
        assert sum(lab[attr] for lab in seen.values()) == counter_sum(
            "ell.slots", mode=mode, **by)
    # each batch's own: the replay of its roots, pad lanes included
    from combblas_tpu.models import PAD_ROOT

    for at, lab in zip(range(0, len(roots), WIDTH), seen.values()):
        lanes = roots[at:at + WIDTH]
        lanes += [PAD_ROOT] * (WIDTH - len(lanes))
        want = _replay(engine, np.asarray(lanes, np.int32))
        assert [lab["slots"], lab["slots_skipped"]] == _busiest(
            want, slots).tolist()
        history, walks = _walks(engine, np.asarray(lanes, np.int32))
        walks = [w for w in walks if w is not None]
        assert [lab["levels"], lab["push_levels"], lab["push_edges"],
                lab["push_passes"]] == [
            len(history), len(walks), int(sum(walks).sum()),
            _passes(engine, np.asarray(lanes, np.int32))]


def test_the_busiest_tile_is_the_one_that_gathered_most():
    """``count_sweep_work`` alone, on a tally made by hand: sweeps over
    all tiles, slots of the tile with the most gathered, class by class."""
    tally = np.zeros((2, 2, 3, 2), np.int64)
    tally[..., 0] = 5  # five iterations, every class dense everywhere ...
    tally[0, 1, 2] = [1, 4]  # ... but the wide class, thin on three tiles
    tally[1, 0, 2] = [0, 5]
    tally[1, 1, 2] = [2, 3]
    tally[1, 1, 0] = [0, 5]  # and one of them skipped the narrow one
    slots = (10, 100, 1000)
    obs.enable(install_hooks=False)
    busiest = count_sweep_work("bfs", 16, tally, slots, phase="x")
    assert busiest.tolist() == [5 * 1110, 0]  # tile (0, 0)
    by = dict(kind="bfs", width=16, phase="x")
    assert [obs.registry.get_counter("ell.slots", cls=c, mode="dense", **by)
            for c in range(3)] == [50, 500, 5000]
    assert [obs.registry.get_counter(
        "ell.class_sweeps", cls=c, mode="skipped", **by)
        for c in range(3)] == [5, 0, 12]
    assert not obs.registry.get_counter("ell.batches", kind="bfs", width=16)
    # a tally padded to another matrix's classes ("bc") weighs the same
    padded = np.concatenate([tally, np.zeros((2, 2, 2, 2), np.int64)], axis=2)
    assert count_sweep_work("bfs", 4, padded, slots).tolist() == (
        busiest.tolist())


def _hubbed():
    """``ragged`` with two hubs more: in-degrees no vertex of it has, so
    the matrix holds other degree classes."""
    rows, cols, n = _graph("ragged")
    far = np.arange(0, 160)
    rows = np.concatenate([rows, np.full(160, 11), np.full(80, 12)])
    cols = np.concatenate([cols, far, far[:80]])
    keep = rows != cols
    pairs = np.unique(np.stack([rows[keep], cols[keep]]), axis=1)
    return pairs[0], pairs[1], n


def test_a_swap_weighs_a_batch_by_the_version_it_was_launched_on():
    """Plans outlive a swap.  A -> B (other classes: one retrace) -> A's
    shapes again (the jit cache hits, nothing retraces): with telemetry
    on every batch is weighed by the class slots of the version it ran
    on, a batch launched before a swap and collected after it too."""
    a, b = _graph("ragged"), _hubbed()
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), *a, kinds=("bfs",), symmetric=False)
    versions = [(a, eng._version)] + [
        (g, eng.build_version(g[0], g[1], symmetric=False)) for g in (b, a)]
    assert len(versions[1][1].E.buckets) != len(eng.E.buckets)
    assert class_slots(versions[2][1].E) == class_slots(eng.E)
    roots = _roots("spread", "ragged", WIDTH)
    obs.enable(install_hooks=False)
    by = dict(kind="bfs", width=WIDTH)
    for at, (graph, version) in enumerate(versions):
        if at:
            eng.swap(version)
        obs.reset()
        with push_capacity(CAPACITY):  # the first two turns trace
            eng.execute("bfs", roots)
        want = _replay(eng, roots, graph)
        slots = class_slots(version.E)
        for m, mode in enumerate(SWEEP_MODES):
            assert counter_sum("ell.slots", mode=mode, **by) == (
                _busiest(want, slots)[m])
            assert counter_sum("ell.class_sweeps", mode=mode, **by) == (
                want[..., m].sum())
    assert eng.plan("bfs", WIDTH).traces == 2
    # launched on A's shapes (the last turn's), collected after a swap
    handle = eng.launch("bfs", roots)
    eng.swap(versions[1][1])
    obs.reset()
    eng.collect(handle)
    assert counter_sum("ell.slots", mode="dense", **by) == _busiest(
        want, slots)[0]
