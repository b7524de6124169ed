"""GAP's BC kernel through the served path: ``Server.submit("bc", root)``
answers one root's Brandes dependency vector and the batch's depth,
held to the plain reference (``chipbench/bcref.py``: float64 Brandes,
the sum rule) on a seeded R-MAT graph with a path, a star, a two-vertex
component and a lone vertex added on vertices the generator left
isolated; the plan's depth against the reference's level count; and the
``serve.bc.*`` counters."""

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
    batch adds that many forward sweeps, one fewer backward, one batch."""
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
                before[0] + levels, before[1] + levels - 1, before[2] + 1]
    finally:
        obs.disable()
        obs.reset()


def test_a_depth_bound_is_counted_as_what_ran(shapes):
    """``max_iters`` below the graph's depth: the forward loop stops at
    the bound with level ``max_iters`` found, so that many forward AND
    backward sweeps ran, and the depth says which levels hold a vertex."""
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
    assert res["batch_niter"] == 3 and got == [2, 2]
    # the bounded answer is Brandes of the first three levels of the path
    assert [res["scores"][v, 0] for v in named["path"]] == [0, 1, 0, 0, 0, 0]
