"""Graph500 kernel 3 through the served path: ``Server.submit("sssp",
root)`` answers a distance array and a parent array, held to the plain
reference (``chipbench/k3ref.py``: exact distances, the specification's
five rules over all edges) on a seeded R-MAT graph with a few hand-made
corners, on a 1x1 and a 2x2 grid; and the parents pass alone against a
numpy argmax."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chipbench import graph  # noqa: E402
from chipbench.k3ref import K3Reference  # noqa: E402
from combblas_tpu.models import PAD_ROOT  # noqa: E402
from combblas_tpu.parallel.grid import Grid  # noqa: E402
from combblas_tpu.serve import GraphEngine, ServeConfig  # noqa: E402

SCALE = 9
GRIDS = [(1, 1), (2, 2)]


@pytest.fixture(scope="module")
def weighted():
    """R-MAT scale 9 with Graph500's weights, plus, on vertices the
    generator left isolated: a two-vertex component ``(a, b)``, a vertex
    ``v`` with two equal shortest paths from ``u`` (through ``x`` and
    ``y``, 1/4 + 1/2 each), and ``lone`` kept isolated."""
    n, rows, cols, _ = graph.rmat_graph(SCALE, 16, 1)
    free = np.flatnonzero(graph.degrees(rows, n) == 0)
    a, b, u, x, y, v, lone = (int(i) for i in free[:7])
    extra = [(a, b, 0.5), (u, x, 0.25), (u, y, 0.25), (x, v, 0.5),
             (y, v, 0.5)]
    r = np.concatenate([rows, [e[0] for e in extra], [e[1] for e in extra]])
    c = np.concatenate([cols, [e[1] for e in extra], [e[0] for e in extra]])
    w = np.concatenate([graph.edge_weights(rows, cols, 1),
                        [e[2] for e in extra] * 2]).astype(np.float32)
    order = np.argsort(r.astype(np.int64) * n + c)
    r, c, w = r[order].astype(np.int32), c[order].astype(np.int32), w[order]
    named = dict(a=a, b=b, u=u, x=x, y=y, v=v, lone=lone)
    return n, r, c, w, named, K3Reference(n, r, c, w)


@pytest.fixture(scope="module", params=GRIDS, ids=lambda g: f"{g[0]}x{g[1]}")
def engine(request, weighted):
    n, r, c, w, _, _ = weighted
    return GraphEngine.from_coo(
        Grid.make(*request.param), r, c, n, weights=w, kinds=("sssp",)
    )


def test_served_answers_are_kernel_3s(engine, weighted):
    """Through ``Server.submit``: distances equal, all five rules, for
    R-MAT roots, a root in a two-vertex component, an isolated root and
    the root with two equal paths to one vertex."""
    n, r, c, _, named, ref = weighted
    roots = [int(x) for x in graph.draw_roots(ref.deg, 7, 3)] + [
        named["a"], named["lone"], named["u"]]
    srv = engine.serve(ServeConfig(lane_widths=(1, 4)))
    srv.warmup(kinds=("sssp",), widths=(1, 4))
    mark = engine.trace_mark()
    srv.start()
    try:
        answers = [f.result(timeout=120) for f in
                   [srv.submit("sssp", root) for root in roots]]
    finally:
        srv.close(drain=False, timeout=5.0)
    assert engine.retraces_since(mark) == 0
    for root, ans in zip(roots, answers):
        assert {"dist", "parents"} <= set(ans)
        assert ans["dist"].shape == ans["parents"].shape == (n,)
        assert ans["dist"].dtype == np.float32
        assert ans["parents"].dtype == np.int32
        assert ref.check_exact(ans["dist"], root) is None
        assert ref.check_tree(ans["dist"], ans["parents"], root) is None
    two, lone, fork = answers[3:]
    assert np.isfinite(two["dist"]).sum() == 2
    assert two["parents"][named["b"]] == named["a"]
    assert np.isfinite(lone["dist"]).sum() == 1
    assert (lone["parents"] >= 0).sum() == 1
    # equal parents: the larger id, as ``_ell_parents_from_levels`` picks
    assert fork["dist"][named["v"]] == 0.75
    assert fork["parents"][named["v"]] == max(named["x"], named["y"])


def test_pad_root_lane_is_inert_beside_live_ones(engine, weighted):
    _, _, _, _, named, ref = weighted
    live = int(graph.draw_roots(ref.deg, 11, 1)[0])
    res = engine.execute(
        "sssp", np.array([live, PAD_ROOT, named["u"], PAD_ROOT], np.int32)
    )
    assert set(res) == {"dist", "parents", "batch_niter"}
    for lane in (1, 3):
        assert np.all(np.isinf(res["dist"][:, lane]))
        assert np.all(res["parents"][:, lane] == -1)
    for lane, root in ((0, live), (2, named["u"])):
        assert ref.check_tree(
            res["dist"][:, lane], res["parents"][:, lane], root) is None
    # the count includes the round that changed nothing
    assert res["batch_niter"] >= 2


def test_parents_pass_alone_is_a_numpy_argmax(engine, weighted):
    """``_ell_minplus_parents`` on reference distances: for every reached
    row the LARGEST neighbour ``j`` with ``d[j] + w(j, v) == d[v]`` (no
    weight here is zero, so ``j`` is strictly nearer and the settling
    rounds, all given as 0, decide nothing); -1 for the root (no
    neighbour closes a path of length 0) and for unreached rows."""
    import jax.numpy as jnp

    from combblas_tpu.parallel.ellmat import _ell_minplus_parents
    from combblas_tpu.parallel.vec import DistMultiVec

    n, r, c, w, named, ref = weighted
    roots = [int(x) for x in graph.draw_roots(ref.deg, 13, 2)] + [named["u"]]
    d = np.stack([ref.distances(x) for x in roots], axis=1).astype(np.float32)
    dv = DistMultiVec.from_global(engine.grid, d, align="row")
    got = DistMultiVec(
        blocks=_ell_minplus_parents(
            engine.E_weighted, dv.blocks,
            jnp.zeros(dv.blocks.shape, jnp.int32)),
        length=n, align="row", grid=engine.grid,
    ).to_global()
    want = np.full((n, len(roots)), -1, np.int64)
    for lane in range(len(roots)):
        dl = d[:, lane].astype(np.float64)
        closes = np.isfinite(dl[r]) & (dl[c] + w == dl[r])
        np.maximum.at(want[:, lane], r[closes], c[closes])
    assert np.array_equal(got, want)
    for lane, root in enumerate(roots):
        assert got[root, lane] == -1


# --- equal distances: zero and absorbed weights ----------------------------

#   0 --1-- 1 --1/2-- 2 ==0== 3 --1/2-- 1     3 ==0== 4 ==0== 5 ==0== 3
#   6 --1-- 7 ~~tiny~~ 8 ~~tiny~~ 9 --1-- 6   (1 + tiny == 1 in float32)
TINY = 2.0 ** -30
TIES = [(0, 1, 1.), (1, 2, .5), (1, 3, .5), (2, 3, 0.), (3, 4, 0.),
        (4, 5, 0.), (5, 3, 0.), (6, 7, 1.), (7, 8, TINY), (8, 9, TINY),
        (9, 6, 1.)]


def ties_coo():
    """``(n, rows, cols, weights)`` of ``TIES``, both directions, sorted
    by (row, col)."""
    n = 10
    r = np.array([e[0] for e in TIES] + [e[1] for e in TIES], np.int32)
    c = np.array([e[1] for e in TIES] + [e[0] for e in TIES], np.int32)
    w = np.array([e[2] for e in TIES] * 2, np.float32)
    order = np.argsort(r * n + c)
    return n, r[order], c[order], w[order]


@pytest.mark.parametrize("dense", [False, True], ids=["thin", "all-dense"])
@pytest.mark.parametrize("grid", GRIDS, ids=lambda g: f"{g[0]}x{g[1]}")
def test_parents_are_a_tree_where_distances_tie(grid, dense, all_dense_sweeps):
    """Graph500 draws weights from [0, 1): an edge of weight zero, or one
    the float sum absorbs, puts both its ends at one distance, and each
    closes a shortest path for the other.  The pick still has to lead to
    the root from every vertex, from every root; and the sweep that
    settles such rows gives the same picks whether it skips the degree
    classes that hold none of them or sweeps them all."""
    all_dense_sweeps(dense)
    n, r, c, w = ties_coo()
    eng = GraphEngine.from_coo(
        Grid.make(*grid), r, c, n, weights=w, kinds=("sssp",))
    roots = np.arange(n, dtype=np.int32)
    res = eng.execute("sssp", roots)
    zero = K3Reference(6, *(a[(r < 6)] for a in (r, c, w)))
    for lane, root in enumerate(roots):
        d, p = res["dist"][:, lane], res["parents"][:, lane]
        reached = np.isfinite(d)
        assert reached.sum() == (6 if root < 6 else 4)
        assert np.all(p[~reached] == -1) and p[root] == root and d[root] == 0
        if root < 6:  # exact in float64 too: the whole of kernel 3's rules
            assert zero.check_exact(d[:6], root) is None
            assert zero.check_tree(d[:6], p[:6], root) is None
        # every pick is an edge that closes a shortest path in the
        # program's own arithmetic, and the picks lead to the root
        for v in np.flatnonzero(reached & (np.arange(n) != root)):
            hit = np.flatnonzero((r == v) & (c == p[v]))
            assert len(hit) == 1 and d[p[v]] + w[hit[0]] == d[v]
        up = np.where(reached, p, np.arange(n))
        for _ in range(4):
            up = up[up]
        assert np.all(up[reached] == root), (root, p)
    # the pick itself, root 0: 3 has a nearer neighbour, 1, and the
    # larger ids 4 and 5, as near, lose to it; 4 and 5 settle in one
    # round, after 3, so neither is the other's parent
    assert list(res["parents"][:6, 0]) == [0, 0, 1, 1, 3, 3]
