"""GAP's BC kernel through the served path: ``Server.submit("bc", root)``
answers one root's Brandes dependency vector and the batch's depth,
held to the plain reference (``chipbench/bcref.py``: float64 Brandes,
the sum rule) on a seeded R-MAT graph with a path, a star, a two-vertex
component and a lone vertex added on vertices the generator left
isolated; the plan's depth against the reference's level count; the
``serve.bc.*`` counters; and the masked sweeps of both loops against the
all-dense program, bit for bit."""

import os
import sys

import ml_dtypes
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chipbench import bcref, graph  # noqa: E402
from combblas_tpu import obs  # noqa: E402
from combblas_tpu.models import PAD_ROOT  # noqa: E402
from combblas_tpu.parallel.grid import Grid  # noqa: E402
from combblas_tpu.serve import GraphEngine, ServeConfig  # noqa: E402

from conftest import counter_sum, idle_classes  # noqa: E402

SCALE = 9
PATH, LEAVES = 6, 5


@pytest.fixture(scope="module")
def shapes():
    """R-MAT scale 9 plus, on isolated vertices: a path of six, a star
    of five leaves, a component of two, and ``lone`` kept isolated."""
    n, rows, cols, _ = graph.rmat_graph(SCALE, 16, 1)
    free = [int(i) for i in np.flatnonzero(graph.degrees(rows, n) == 0)]
    path, free = free[:PATH], free[PATH:]
    centre, leaves, free = free[0], free[1:1 + LEAVES], free[1 + LEAVES:]
    a, b, lone = free[:3]
    extra = (list(zip(path, path[1:])) + [(centre, x) for x in leaves]
             + [(a, b)])
    r = np.concatenate([rows, [e[0] for e in extra], [e[1] for e in extra]])
    c = np.concatenate([cols, [e[1] for e in extra], [e[0] for e in extra]])
    order = np.argsort(r.astype(np.int64) * n + c)
    r, c = r[order].astype(np.int32), c[order].astype(np.int32)
    named = dict(path=path, centre=centre, leaves=leaves, a=a, b=b,
                 lone=lone)
    return n, r, c, named, bcref.BCReference(n, r, c)


@pytest.fixture(scope="module")
def engine(shapes):
    n, r, c, _, _ = shapes
    return GraphEngine.from_coo(Grid.make(1, 1), r, c, n, kinds=("bc",))


def _roots(shapes) -> dict:
    _, _, _, named, ref = shapes
    return {
        "rmat": [int(x) for x in graph.draw_roots(ref.bfs.deg, 7, 4)],
        "path": [named["path"][0], named["path"][2]],
        "star": [named["leaves"][0], named["centre"]],
        "components": [named["a"], named["lone"]],
    }


@pytest.fixture(scope="module")
def served(engine, shapes):
    """Every case's roots through ``Server.submit_many`` (one GAP trial)
    and ``Server.submit``, the scheduler and the batcher, in 16-wide
    batches with pad lanes: ``{root: answer}``, and the retraces."""
    cases = _roots(shapes)
    srv = engine.serve(ServeConfig(lane_widths=(16,)))
    srv.warmup(kinds=("bc",), widths=(16,))
    mark = engine.trace_mark()
    srv.start()
    try:
        futures = dict(zip(cases["rmat"],
                           srv.submit_many("bc", cases["rmat"])))
        for case in ("path", "star", "components"):
            for root in cases[case]:
                futures[root] = srv.submit("bc", root)
        answers = {root: f.result(timeout=300)
                   for root, f in futures.items()}
    finally:
        srv.close(drain=False, timeout=5.0)
    return answers, engine.retraces_since(mark)


@pytest.mark.parametrize("case", ["rmat", "path", "star", "components"])
def test_served_answers_are_brandes(served, shapes, case):
    _, _, _, named, ref = shapes
    answers, retraces = served
    assert retraces == 0
    for root in _roots(shapes)[case]:
        res = answers[root]
        assert set(res) == {"scores", "batch_niter"}
        scores = res["scores"]
        assert scores.shape == (ref.n,) and scores.dtype == np.float32
        assert bcref.check_answer(scores, root, ref.bfs.deg) is None
        assert ref.check_exact(scores, root) is None
        assert ref.check_sum(scores, root) is None
        # the batch ran as deep as its deepest lane
        assert res["batch_niter"] >= int(ref.levels(root).max()) + 1
    if case == "path":  # from an end: everything beyond v depends on v
        end, third = (answers[r]["scores"] for r in _roots(shapes)[case])
        assert [end[v] for v in named["path"]] == [0, 4, 3, 2, 1, 0]
        # from the third: two behind it through the second, and ahead
        assert [third[v] for v in named["path"]] == [0, 1, 0, 2, 1, 0]
    if case == "star":  # a leaf reaches the other four through the centre
        leaf, centre = (answers[r]["scores"] for r in _roots(shapes)[case])
        assert leaf[named["centre"]] == LEAVES - 1 and leaf.sum() == LEAVES - 1
        assert not centre.any()
    if case == "components":
        for root in _roots(shapes)[case]:
            assert not answers[root]["scores"].any()


def test_a_trial_is_the_sum_of_its_four_answers(served, engine, shapes):
    """GAP's four-root scores three ways: the client's sum of four served
    answers, ``bc_batch_dense`` of the four roots, the reference's."""
    import jax.numpy as jnp

    from combblas_tpu.models.bc import bc_batch_dense

    _, _, _, _, ref = shapes
    roots = _roots(shapes)["rmat"]
    total = sum(served[0][r]["scores"].astype(np.float64) for r in roots)
    assert ref.check_trial(total, roots) is None
    direct = bc_batch_dense(
        engine.E, engine.ET, jnp.asarray(roots, jnp.int32)).to_global()
    np.testing.assert_allclose(total, direct, rtol=1e-5, atol=1e-6)
    other = sum(served[0][r]["scores"].astype(np.float64)
                for r in roots[:3])
    assert "trial" in ref.check_trial(other, roots)


@pytest.mark.parametrize("planted", ["one score", "an unreached vertex",
                                     "bfloat16"])
def test_the_checks_catch_a_planted_error(served, shapes, planted):
    _, _, _, named, ref = shapes
    root = _roots(shapes)["rmat"][0]
    good = served[0][root]["scores"]
    assert ref.check_sum(good, root) is None
    assert ref.check_sum(ref.dependencies_held_in(root, np.float32),
                         root) is None
    if planted == "one score":
        bad = good.copy()
        bad[int(np.argmax(good))] *= np.float32(1.01)
        assert "reference says" in ref.check_exact(bad, root)
        assert "sum rule" in ref.check_sum(bad, root)
    elif planted == "an unreached vertex":
        bad = good.copy()
        bad[named["lone"]] = 1e-3
        assert "not reached" in ref.check_sum(bad, root)
        assert "without an edge" in bcref.check_answer(
            bad, root, ref.bfs.deg)
        assert "itself" in bcref.check_answer(
            np.where(np.arange(ref.n) == root, 1, good), root, ref.bfs.deg)
        assert "negative" in bcref.check_answer(-good, root, ref.bfs.deg)
    else:  # the chip's precision below float32
        bad = ref.dependencies_held_in(root, ml_dtypes.bfloat16)
        assert "reference says" in ref.check_exact(bad, root)
        assert ref.worst(bad, ref.dependencies(root)) > 4 * bcref.RTOL
        assert "sum rule" in ref.check_sum(bad, root)


def test_the_plan_returns_its_depth_and_counts_its_sweeps(engine, shapes):
    """``engine.execute("bc", ...)``: ``batch_niter`` is the reference's
    level count of the deepest live lane, and with telemetry on one
    batch adds that many forward sweeps, two fewer backward (the deepest
    level exports to nothing, the roots' level is not updated), one
    batch."""
    _, _, _, named, ref = shapes
    deep = [named["path"][0], _roots(shapes)["rmat"][0], PAD_ROOT,
            named["lone"]]
    flat = [named["centre"], named["lone"], PAD_ROOT, PAD_ROOT]

    def counted(phase=None):
        name = "serve.bc.sweeps" if phase else "serve.bc.batches"
        labels = dict(phase=phase, width=4) if phase else dict(width=4)
        return obs.registry.get_counter(name, **labels)

    engine.execute("bc", np.asarray(flat, np.int32))  # telemetry off
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        assert counted() == 0
        for srcs, levels in ((deep, PATH), (flat, 2)):
            assert ref.level_count([s for s in srcs if s != PAD_ROOT]
                                   ) == levels
            before = [counted("forward"), counted("backward"), counted()]
            res = engine.execute("bc", np.asarray(srcs, np.int32))
            assert res["batch_niter"] == levels
            assert [counted("forward"), counted("backward"), counted()] == [
                before[0] + levels, before[1] + levels - 2, before[2] + 1]
    finally:
        obs.disable()
        obs.reset()


def test_a_depth_bound_is_counted_as_what_ran(shapes):
    """``max_iters`` below the graph's depth: the forward loop stops at
    the bound with level ``max_iters`` found, so that many forward sweeps
    ran and one fewer backward (level ``max_iters`` exports, the roots'
    level is not updated), and the depth says which levels hold a
    vertex."""
    n, r, c, named, _ = shapes
    bounded = GraphEngine.from_coo(
        Grid.make(1, 1), r, c, n, kinds=("bc",), max_iters=2)
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        res = bounded.execute(
            "bc", np.asarray([named["path"][0], PAD_ROOT], np.int32))
        got = [obs.registry.get_counter(
            "serve.bc.sweeps", phase=p, width=2)
            for p in ("forward", "backward")]
    finally:
        obs.disable()
        obs.reset()
    assert res["batch_niter"] == 3 and got == [2, 1]
    # the bounded answer is Brandes of the first three levels of the path
    assert [res["scores"][v, 0] for v in named["path"]] == [0, 1, 0, 0, 0, 0]


# --- PR 31: both loops sweep through the class-choosing masked sweep --------


def _masked_case(shapes, case):
    """``(sources, grid shape, max_depth)``: 16 lanes of the module's
    graph."""
    _, _, _, named, ref = shapes
    live = [int(x) for x in graph.draw_roots(ref.bfs.deg, 11, 16)]
    pad = [PAD_ROOT]
    return {
        "16 live roots": (live, (1, 1), None),
        # the pair's lane ends after two levels, the star's after three
        "unequal depths": (
            [named["a"], named["leaves"][0], named["centre"]] + live[:13],
            (1, 1), None),
        # the path's end runs six levels: the R-MAT lanes have ended, and
        # every row they reached is one its lane has not
        "a long lane alone": (
            [named["path"][0], named["leaves"][0], named["a"], live[0]]
            + [named["path"][3], named["centre"]] * 6, (1, 1), None),
        "pad lanes": (live[:5] + pad * 11, (1, 1), None),
        "depth bound": (live[:8] + [named["path"][0]] + pad * 7, (1, 1), 2),
        "no edge": ([named["lone"]] * 3 + pad * 13, (1, 1), None),
        "2x2 grid": (
            live[:6] + [named["path"][1], named["lone"]] + pad * 8,
            (2, 2), None),
        "2x2 grid, depth bound": (live[:12] + pad * 4, (2, 2), 3),
    }[case]


def _numpy_skips(E, ref, sources, forward, cut):
    """``int[2, pr, pc, classes]``: how often each tile skips each degree
    class in the ``forward`` sweeps of a batch and in the backward ones
    after them (``cut``: the depth bound ended the forward loop), from the
    reference's BFS levels and ``E``'s own bucket rows.  A sweep's table
    is non-zero on the vertices AT a level; going out the mask keeps the
    rows no lane has reached yet, going back those one level nearer; a
    tile skips a class none of whose rows the mask keeps in a lane whose
    table is not all zero in the tile's column block
    (``conftest.idle_classes``)."""
    n = ref.n
    lvl = np.full((n, len(sources)), -1)
    for lane, root in enumerate(sources):
        if root != PAD_ROOT:
            lv = ref.levels(root)
            lvl[:, lane] = np.where((lv >= 0) & (lv <= forward), lv, -1)
    out = np.zeros((2, E.grid.pr, E.grid.pc, len(E.buckets)), int)
    for d in range(forward):
        out[0] += idle_classes(E, lvl == d, (lvl < 0) | (lvl > d))
    for d in range(forward - (0 if cut else 1), 1, -1):
        out[1] += idle_classes(E, lvl == d, lvl == d - 1)
    return out


@pytest.mark.parametrize("case", [
    "16 live roots", "unequal depths", "a long lane alone", "pad lanes",
    "depth bound", "no edge", "2x2 grid", "2x2 grid, depth bound"])
def test_masked_sweeps_answer_as_all_dense(
        shapes, all_dense_sweeps, no_class_idle, case):
    """Both loops of ``_bc_batch_lanes`` skip the degree classes none of
    whose rows a level can change, and the dependencies and the depth
    are BIT FOR BIT those of the same program with every class swept
    (``no_class_idle``); ``sweeps`` counts what ran (forward one a level,
    backward none for the deepest level on a natural exit and none for
    the roots'), and every sweep's classes are all in the tally, on every
    tile.  Against the program with no choice in it (``all_dense_sweeps``)
    depth, sweeps and which entries are zero are equal, and the float32
    sums to the last bits: XLA:CPU folds a class inside a branch in
    another order than outside one."""
    import jax
    import jax.numpy as jnp

    from combblas_tpu.models.bc import _bc_batch_lanes
    from combblas_tpu.parallel.ellmat import EllParMat

    n, r, c, _, ref = shapes
    sources, shape, max_depth = _masked_case(shapes, case)
    E = EllParMat.from_host_coo(
        Grid.make(*shape), r, c, np.ones(len(r), np.float32), n, n)
    srcs = jnp.asarray(sources, jnp.int32)

    def run():
        return [np.asarray(a) for a in jax.jit(
            lambda E, s: _bc_batch_lanes(E, E, s, max_depth))(E, srcs)]

    all_dense_sweeps(True)
    unbranched = run()
    all_dense_sweeps(False)
    no_class_idle(True)
    swept = run()
    no_class_idle(False)
    delta, depth, sweeps, by_class = run()

    assert delta.dtype == np.float32
    np.testing.assert_array_equal(
        delta.view(np.uint32), swept[0].view(np.uint32))
    np.testing.assert_array_equal(delta == 0, unbranched[0] == 0)
    np.testing.assert_allclose(delta, unbranched[0], rtol=2e-6, atol=0)
    for other in (swept, unbranched):
        assert depth == other[1] and sweeps.tolist() == other[2].tolist()
    classes = len(E.buckets)
    assert by_class.shape == (2, *shape, classes, 2)  # phase, tile, class
    assert not unbranched[3].any()  # no class chose, no tally
    assert not swept[3][..., 1].any()  # every class chose, none skipped
    np.testing.assert_array_equal(
        swept[3].sum(axis=-1), by_class.sum(axis=-1))

    levels = ref.level_count([s for s in sources if s != PAD_ROOT])
    cut = max_depth is not None and max_depth < levels - 1
    assert depth == (max_depth + 1 if cut else levels)
    forward = max_depth if cut else levels
    assert sweeps.tolist() == [
        forward, max(forward - (1 if cut else 2), 0)]
    # a choice a sweep, tile and class; which were skipped is what a
    # numpy replay of the two loops' masks finds, entry for entry
    for phase, ran in enumerate(sweeps):
        assert (by_class[phase].sum(axis=-1) == ran).all()
    np.testing.assert_array_equal(
        by_class[..., 1], _numpy_skips(E, ref, sources, forward, cut))
    # which loops thinned: (forward, backward)
    thinned = (bool(by_class[0, ..., 1].any()), bool(by_class[1, ..., 1].any()))
    assert thinned == {
        # a lone root's lane is live for its one sweep: nothing is reached
        "no edge": (False, False),
        # levels 0-2 going out and level 2 going back: every class busy
        "depth bound": (False, False),
        # a live lane in a small component leaves every other row
        # unreached: going out nothing thins, going back all but its rows
        "a long lane alone": (False, True),
    }.get(case, (True, True)), by_class
    if case == "a long lane alone":
        assert by_class[1, ..., 1].sum() >= by_class[1, ..., 0].sum()


def test_class_sweeps_are_counted_with_telemetry_on(engine, shapes):
    """A served batch adds classes x sweeps to
    ``ell.class_sweeps{kind=bc, width, phase, mode}`` and the same counts
    weighed by the swept matrix's ``class_slots`` to ``ell.slots``; with
    telemetry off nothing is read back and the registry stays empty."""
    import jax.numpy as jnp

    from combblas_tpu.models.bc import BC_PHASES
    from combblas_tpu.parallel.ellmat import SWEEP_MODES, class_slots

    _, _, _, named, _ = shapes
    srcs = np.asarray([named["path"][0], _roots(shapes)["rmat"][0],
                       PAD_ROOT, named["lone"]], np.int32)

    def counted(series):
        return {
            (p, m): counter_sum(series, kind="bc", width=4, phase=p, mode=m)
            for p in BC_PHASES for m in SWEEP_MODES}

    obs.reset()
    engine.execute("bc", srcs)  # telemetry off
    assert obs.registry.snapshot() == []
    obs.enable(install_hooks=False)
    try:
        engine.execute("bc", srcs)
        got, slots = counted("ell.class_sweeps"), counted("ell.slots")
        assert obs.registry.get_counter("ell.batches", kind="bc", width=4) == 1
    finally:
        obs.disable()
        obs.reset()
    classes = len(engine.E.buckets)
    # a symmetric graph: E is its own transpose, which both phases sweep
    weights = class_slots(engine.E)
    assert engine._swept("bc") == tuple(
        ({"phase": phase}, weights) for phase in BC_PHASES)
    tally = np.asarray(engine.plan("bc", 4).fn(jnp.asarray(srcs))[3])
    for p, phase in enumerate(BC_PHASES):
        by_class = tally[p, 0, 0]
        assert by_class.sum(axis=0).tolist() == [
            got[phase, m] for m in SWEEP_MODES]
        assert (np.asarray(weights) @ by_class).tolist() == [
            slots[phase, m] for m in SWEEP_MODES]
    for phase, ran in (("forward", PATH), ("backward", PATH - 2)):
        assert got[phase, "dense"] + got[phase, "skipped"] == classes * ran
    # the path's lane runs on alone: going out it keeps every class busy
    # (no row of the R-MAT component is reached in it), going back it
    # alone is swept
    assert got["forward", "skipped"] == 0
    assert got["backward", "skipped"] >= got["backward", "dense"] > 0
