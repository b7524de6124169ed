"""The served Bellman-Ford round sweeps only the degree classes that hold
a row it can still lower (PR 33): an entry falls only from ABOVE the
smallest distance the round before lowered in its lane, so the round
hands ``ellmat.ell_masked_multi_sweep`` that mask.  Held here to the same
program with every class swept (``all_dense_sweeps``), with ``==``, on an
R-MAT graph with Graph500's weights and on the zero and absorbed weights
of ``test_sssp_k3.py``, on a 1x1 and a 2x2 grid; the tally it returns and
the counter the engine reads it into."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chipbench import graph  # noqa: E402
from combblas_tpu import obs  # noqa: E402
from combblas_tpu.models import PAD_ROOT  # noqa: E402
from combblas_tpu.parallel.ellmat import SWEEP_MODES  # noqa: E402
from combblas_tpu.parallel.grid import Grid  # noqa: E402
from combblas_tpu.serve import GraphEngine  # noqa: E402

from conftest import counter_sum, idle_classes  # noqa: E402
from test_sssp_k3 import ties_coo  # noqa: E402

SCALE = 9
GRIDS = [(1, 1), (2, 2)]


def _coo(case):
    """``(n, rows, cols, weights, roots)``, sorted by (row, col)."""
    if case == "rmat":
        n, r, c, _ = graph.rmat_graph(SCALE, 16, 1)
        w = graph.edge_weights(r, c, 1).astype(np.float32)
        deg = graph.degrees(r, n)
        live = [int(x) for x in graph.draw_roots(deg, 2300001111, 5)]
        lone = int(np.flatnonzero(deg == 0)[0])
        # a repeated root, a PAD_ROOT lane, a root with no edge (its lane
        # is finished after one round)
        return n, r, c, w, live[:3] + [live[0], PAD_ROOT, lone] + live[3:]
    n, r, c, w = ties_coo()
    return n, r, c, w, list(range(n))


def _run(engine, roots):
    """``(dist, parents, rounds, class sweeps)`` of the served width-W
    plan, on the host."""
    import jax.numpy as jnp

    out = engine.plan("sssp", len(roots)).fn(
        jnp.asarray(np.asarray(roots, np.int32)))
    return [np.asarray(o) for o in out]


def _host_rounds(n, r, c, w, roots):
    """Synchronous Bellman-Ford in float32 on the host, a round at a
    time: ``(d, active, lowered)``, the distances the round starts from,
    the mask the program gives its sweep (entries above the smallest
    distance the round before lowered in their lane) and what the round
    lowered; the round that lowers nothing is the last."""
    inf = np.float32(np.inf)
    d = np.full((n, len(roots)), inf, np.float32)
    for lane, root in enumerate(roots):
        if root != PAD_ROOT:
            d[root, lane] = 0
    settled = np.zeros(d.shape, np.int32)
    for it in range(n):
        floor = np.where(settled == it, d, inf).min(axis=0)
        relaxed = np.full_like(d, inf)
        np.minimum.at(relaxed, r, d[c] + w[:, None])
        nd = np.minimum(d, relaxed)
        lowered = nd != d
        yield d, d > floor, lowered
        if not lowered.any():
            return
        settled = np.where(lowered, it + 1, settled)
        d = nd


def _host_skips(E, rounds):
    """``bool[rounds, pr, pc, classes]``: the class sweeps a tile skips,
    by round: a class none of whose rows is active in a lane that holds a
    finite distance in the tile's column block
    (``conftest.idle_classes``)."""
    return np.asarray(
        [idle_classes(E, np.isfinite(d), active) for d, active, _ in rounds])


@pytest.mark.parametrize("case", ["rmat", "ties"])
@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: f"{g[0]}x{g[1]}")
def test_masked_rounds_give_the_all_dense_answer(grid, case, all_dense_sweeps):
    """Same ``dist`` (bit for bit: compared as the int32 behind each
    float), ``parents`` and ``rounds`` with the mask and with every class
    swept; the tally is rounds x classes x tiles with the mask and empty
    without, and its skipped count is the host's, round by round; on the
    R-MAT graph a class is skipped in a round that still lowers a
    distance (the mechanism engages before the last round, in which
    nothing falls)."""
    n, r, c, w, roots = _coo(case)
    eng = GraphEngine.from_coo(
        Grid.make(*grid), r, c, n, weights=w, kinds=("sssp",))
    dist, parents, rounds, tally = _run(eng, roots)
    all_dense_sweeps(True)
    dense = GraphEngine.from_coo(
        Grid.make(*grid), r, c, n, weights=w, kinds=("sssp",))
    ddist, dparents, drounds, dtally = _run(dense, roots)
    assert np.array_equal(dist.view(np.int32), ddist.view(np.int32))
    assert np.array_equal(parents, dparents)
    assert rounds == drounds >= 2
    E = eng.E_weighted
    assert tally.shape == (*grid, len(E.buckets), len(SWEEP_MODES))
    assert not dtally.any()
    # a choice a tile, class and round; the skipped ones are the host's,
    # tile by tile and class by class
    assert (tally.sum(axis=-1) == rounds).all()
    skips = _host_skips(E, _host_rounds(n, r, c, w, roots))
    assert len(skips) == rounds
    assert np.array_equal(skips.sum(axis=0), tally[..., 1])
    if case == "rmat":
        assert skips[:-1].any() and tally[..., 0].any(), skips


def test_a_finished_lane_keeps_no_row_active():
    """What the round's mask is made of, on the host: the floor of a lane
    that lowered nothing in the round before is ``+inf`` and no entry is
    above it, a ``PAD_ROOT`` lane's likewise; and every entry a round
    lowers is one its mask kept."""
    n, r, c, w, _ = _coo("rmat")
    deg = graph.degrees(r, n)
    root = int(graph.draw_roots(deg, 5, 1)[0])
    lone = int(np.flatnonzero(deg == 0)[0])
    fell = 0
    for it, (_, active, lowered) in enumerate(
            _host_rounds(n, r, c, w, [root, lone, PAD_ROOT])):
        assert not active[:, 2].any()
        # the lone root's lane lowers nothing in round 1 and is finished
        assert it == 0 or not active[:, 1].any()
        assert not (lowered & ~active).any()
        fell += int(lowered.sum())
    assert fell > n // 2 and it >= 3


def test_class_sweeps_are_counted_with_telemetry_on():
    """A served batch adds rounds x classes (x tiles) to
    ``ell.class_sweeps{kind=sssp, width, mode}`` beside
    ``serve.sssp.rounds``, and the same counts weighed by the plan's
    ``class_slots`` to ``ell.slots``; with telemetry off the tally is not
    read back and the registry stays empty."""
    from combblas_tpu.parallel.ellmat import class_slots

    n, r, c, w, roots = _coo("rmat")
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), r, c, n, weights=w, kinds=("sssp",))
    srcs = np.asarray(roots[:8], np.int32)

    def counted(series):
        return {m: counter_sum(series, kind="sssp", width=8, mode=m)
                for m in SWEEP_MODES}

    obs.reset()
    eng.execute("sssp", srcs)  # telemetry off
    assert obs.registry.snapshot() == []
    obs.enable(install_hooks=False)
    try:
        res = eng.execute("sssp", srcs)
        got, slots = counted("ell.class_sweeps"), counted("ell.slots")
        obs_counts = {
            (i, m): obs.registry.get_counter(
                "ell.slots", kind="sssp", width=8, cls=i, mode=m)
            for i in range(len(eng.E_weighted.buckets)) for m in SWEEP_MODES}
        rounds = obs.registry.get_counter("serve.sssp.rounds", width=8)
        batches = obs.registry.get_counter(
            "ell.batches", kind="sssp", width=8)
    finally:
        obs.disable()
        obs.reset()
    assert rounds == res["batch_niter"] and batches == 1
    assert got["dense"] + got["skipped"] == rounds * len(
        eng.E_weighted.buckets)
    assert got["skipped"] > 0 and got["dense"] > 0
    # the weights of the matrix the rounds sweep, and what the program
    # counted by class
    weights = class_slots(eng.E_weighted)
    assert eng._swept("sssp") == (({}, weights),)
    tally = _run(eng, srcs)[3][0, 0]
    assert tally.sum(axis=0).tolist() == [got["dense"], got["skipped"]]
    assert [slots[m] for m in SWEEP_MODES] == (
        np.asarray(weights) @ tally).tolist()
    # class by class too: the family's series carry the class
    for i, size in enumerate(weights):
        assert [obs_counts[i, m] for m in SWEEP_MODES] == (
            size * tally[i]).tolist()
    assert slots["dense"] + slots["skipped"] == rounds * sum(weights)
