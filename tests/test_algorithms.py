"""Algorithm pack 1: CC (FastSV), SSSP, PageRank, TC, MIS vs trusted refs.

Mirrors the reference's self-checking app-test pattern (SURVEY.md §4.3):
random/er inputs, results validated against an independent implementation
(scipy.sparse.csgraph / dense numpy) instead of golden files.
"""

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.csgraph as csgraph

from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.parallel.vec import DistVec
from combblas_tpu.semiring import MIN_PLUS, PLUS_TIMES, SELECT2ND_MIN


def sym_graph(rng, n, density=0.05, weighted=False):
    """Random symmetric loop-free graph as (dense, rows, cols, vals)."""
    d = (rng.random((n, n)) < density).astype(np.float32)
    if weighted:
        d *= np.round(rng.random((n, n)) * 9 + 1).astype(np.float32)
    d = np.triu(d, 1)
    d = d + d.T
    r, c = np.nonzero(d)
    return d, r, c, d[r, c]


@pytest.mark.parametrize("pr,pc", [(2, 2), (2, 4)])
def test_connected_components_vs_scipy(rng, pr, pc):
    from combblas_tpu.models.cc import connected_components, num_components

    grid = Grid.make(pr, pc)
    n = 60
    # sparse enough to have several components
    d, r, c, v = sym_graph(rng, n, density=0.02)
    A = SpParMat.from_global_coo(grid, r, c, v, n, n, dedup_sr=PLUS_TIMES)
    labels, niter = connected_components(A)
    lab = labels.to_global()

    ncomp_ref, lab_ref = csgraph.connected_components(
        sp.csr_matrix(d), directed=False
    )
    assert num_components(labels) == ncomp_ref
    # same partition: our labels constant on each reference component
    for comp in range(ncomp_ref):
        assert len(np.unique(lab[lab_ref == comp])) == 1
    # label = min vertex id of the component
    for comp in range(ncomp_ref):
        members = np.flatnonzero(lab_ref == comp)
        assert lab[members[0]] == members.min()


def test_cc_all_isolated(rng):
    from combblas_tpu.models.cc import connected_components

    grid = Grid.make(2, 2)
    n = 16
    # single undirected edge {0,1} (stored symmetrically), rest isolated
    A = SpParMat.from_global_coo(grid, [0, 1], [1, 0], [1.0, 1.0], n, n)
    labels, _ = connected_components(A)
    lab = labels.to_global()
    assert lab[0] == lab[1] == 0
    assert all(lab[i] == i for i in range(2, n))


@pytest.mark.parametrize("pr,pc", [(2, 2)])
def test_sssp_vs_scipy(rng, pr, pc):
    from combblas_tpu.models.sssp import sssp

    grid = Grid.make(pr, pc)
    n = 50
    d, r, c, v = sym_graph(rng, n, density=0.08, weighted=True)
    A = SpParMat.from_global_coo(grid, r, c, v, n, n, dedup_sr=MIN_PLUS)
    dist, niter = sssp(A, 0)
    got = dist.to_global()

    ref = csgraph.dijkstra(sp.csr_matrix(d), directed=False, indices=0)
    np.testing.assert_allclose(got, ref.astype(np.float32), rtol=1e-6)


def test_sssp_directed_line():
    from combblas_tpu.models.sssp import sssp

    grid = Grid.make(2, 2)
    n = 8
    # path 0 -> 1 -> 2 -> 3 with weights 1,2,3; A[i,j] = w(j->i)
    r = np.array([1, 2, 3])
    c = np.array([0, 1, 2])
    v = np.array([1.0, 2.0, 3.0], np.float32)
    A = SpParMat.from_global_coo(grid, r, c, v, n, n)
    dist, _ = sssp(A, 0)
    got = dist.to_global()
    assert got[0] == 0 and got[1] == 1 and got[2] == 3 and got[3] == 6
    assert np.isinf(got[4:]).all() or (got[4:] >= np.finfo(np.float32).max).all()


@pytest.mark.parametrize("pr,pc", [(2, 2), (4, 2)])
def test_pagerank_vs_dense(rng, pr, pc):
    from combblas_tpu.models.pagerank import pagerank

    grid = Grid.make(pr, pc)
    n = 40
    # directed graph with some dangling nodes
    d = (rng.random((n, n)) < 0.06).astype(np.float32)
    np.fill_diagonal(d, 0)
    d[:, -3:] = 0  # dangling columns
    r, c = np.nonzero(d)
    A = SpParMat.from_global_coo(grid, r, c, d[r, c], n, n)
    ranks, niter = pagerank(A, alpha=0.85, tol=1e-10, max_iters=200)
    got = ranks.to_global()

    # dense reference power iteration
    outdeg = d.sum(axis=0)
    P = np.divide(d, outdeg, where=outdeg > 0, out=np.zeros_like(d))
    x = np.full(n, 1.0 / n)
    for _ in range(200):
        dmass = x[outdeg == 0].sum()
        x_new = 0.85 * (P @ x) + (0.15 + 0.85 * dmass) / n
        if np.abs(x_new - x).sum() < 1e-12:
            x = x_new
            break
        x = x_new
    np.testing.assert_allclose(got, x, atol=1e-5)


def test_pagerank_batch_personalized_vs_dense(rng):
    """W personalized-PageRank chains in one program vs a dense reference
    per source."""
    import jax.numpy as jnp

    from combblas_tpu.models.pagerank import pagerank_batch
    from combblas_tpu.parallel.ellmat import EllParMat
    from combblas_tpu.parallel.vec import DistVec

    grid = Grid.make(2, 2)
    n = 40
    d = (rng.random((n, n)) < 0.08).astype(np.float32)
    np.fill_diagonal(d, 0)
    d[:, -3:] = 0  # dangling columns
    r, c = np.nonzero(d)
    outdeg = d.sum(axis=0)
    vals = 1.0 / outdeg[c]  # column-normalized host-side
    P_ell = EllParMat.from_host_coo(
        grid, r.astype(np.int64), c.astype(np.int64),
        vals.astype(np.float32), n, n,
    )
    dang = DistVec.from_global(
        grid, (outdeg == 0).astype(np.float32), align="col"
    )
    sources = jnp.asarray([0, 7, 19, 33], jnp.int32)
    ranks, niter = pagerank_batch(
        P_ell, sources, dang, alpha=0.85, tol=1e-10, max_iters=300
    )
    got = ranks.to_global()  # [n, W]
    assert int(niter) > 1

    P = np.divide(d, outdeg, where=outdeg > 0, out=np.zeros_like(d))
    for w, s in enumerate([0, 7, 19, 33]):
        e = np.zeros(n)
        e[s] = 1.0
        x = e.copy()
        for _ in range(300):
            dmass = x[outdeg == 0].sum()
            x_new = 0.85 * (P @ x + dmass * e) + 0.15 * e
            if np.abs(x_new - x).sum() < 1e-12:
                break
            x = x_new
        np.testing.assert_allclose(got[:, w], x, atol=1e-5)
        assert abs(got[:, w].sum() - 1.0) < 1e-4


@pytest.mark.parametrize("pr,pc", [(2, 2)])
def test_triangle_count_vs_dense(rng, pr, pc):
    from combblas_tpu.models.tc import triangle_count

    grid = Grid.make(pr, pc)
    n = 40
    d, r, c, v = sym_graph(rng, n, density=0.15)
    A = SpParMat.from_global_coo(grid, r, c, v, n, n, dedup_sr=PLUS_TIMES)
    got = triangle_count(A)
    b = (d != 0).astype(np.int64)
    ref = int(np.trace(b @ b @ b) // 6)
    assert got == ref
    assert ref > 0  # density chosen so the test is non-vacuous


def test_triangle_count_known():
    from combblas_tpu.models.tc import triangle_count

    grid = Grid.make(2, 2)
    # K4 has 4 triangles
    n = 6
    d = np.zeros((n, n), np.float32)
    d[:4, :4] = 1 - np.eye(4)
    r, c = np.nonzero(d)
    A = SpParMat.from_global_coo(grid, r, c, d[r, c], n, n)
    assert triangle_count(A) == 4


@pytest.mark.parametrize("pr,pc", [(2, 2), (2, 4)])
def test_mis_independent_and_maximal(rng, pr, pc):
    import jax

    from combblas_tpu.models.mis import mis

    grid = Grid.make(pr, pc)
    n = 60
    d, r, c, v = sym_graph(rng, n, density=0.08)
    A = SpParMat.from_global_coo(grid, r, c, v, n, n, dedup_sr=PLUS_TIMES)
    status, niter = mis(A, jax.random.key(3))
    s = status.to_global()
    in_set = np.flatnonzero(s == 1)
    assert in_set.size > 0
    # independence: no edge inside the set
    assert d[np.ix_(in_set, in_set)].sum() == 0
    # maximality: every excluded vertex has a neighbor in the set
    excluded = np.flatnonzero(s == -1)
    for v_ in excluded:
        assert d[v_, in_set].sum() > 0, f"vertex {v_} has no MIS neighbor"


def test_gather_scatter_roundtrip(rng):
    grid = Grid.make(2, 2)
    n = 23
    x = DistVec.from_global(grid, np.arange(100, 100 + n, dtype=np.int32))
    idx = DistVec.from_global(
        grid, rng.integers(0, n, size=n).astype(np.int32)
    )
    g = x.gather(idx)
    np.testing.assert_array_equal(
        g.to_global(), (np.arange(100, 100 + n))[idx.to_global()]
    )

    # scatter-min: out[p] = min(base[p], min of src where idx==p)
    base = DistVec.from_global(grid, np.full(n, 1000, np.int32))
    src = DistVec.from_global(grid, np.arange(n, dtype=np.int32))
    out = base.scatter_combine(SELECT2ND_MIN, idx=idx, src=src)
    ref = np.full(n, 1000, np.int64)
    np.minimum.at(ref, idx.to_global(), np.arange(n))
    np.testing.assert_array_equal(out.to_global(), ref.astype(np.int32))


def test_tril_triu_remove_loops(rng):
    grid = Grid.make(2, 2)
    n = 17
    d = (rng.random((n, n)) < 0.3).astype(np.float32)
    r, c = np.nonzero(d)
    A = SpParMat.from_global_coo(grid, r, c, d[r, c], n, n)
    np.testing.assert_array_equal(A.tril().to_dense(), np.tril(d, -1))
    np.testing.assert_array_equal(A.triu().to_dense(), np.triu(d, 1))
    np.testing.assert_array_equal(
        A.tril(strict=False).to_dense(), np.tril(d)
    )
    nl = A.remove_loops().to_dense()
    ref = d.copy()
    np.fill_diagonal(ref, 0)
    np.testing.assert_array_equal(nl, ref)


@pytest.mark.parametrize("shape", [(2, 2), (2, 4)])
def test_lacc_matches_fastsv(rng, shape):
    """LACC (real implementation) labels the same partition as FastSV on
    random graphs including isolated vertices (the reference's ctest
    equivalence role for CC algorithms)."""
    from combblas_tpu.models.cc import connected_components, lacc

    grid = Grid.make(*shape)
    n = 40
    d = (rng.random((n, n)) < 0.06)
    d = (d | d.T).astype(np.float32)
    np.fill_diagonal(d, 0)
    d[:, 7] = 0; d[7, :] = 0  # force an isolated vertex
    A = SpParMat.from_dense(grid, d)
    l1, _ = connected_components(A)
    l2, _ = lacc(A)
    a = l1.to_global()
    b = l2.to_global()
    # same partition: labels equal up to renaming — both use min-id roots,
    # but compare as partitions to be robust
    import itertools
    part_a = {}
    for v, lab in enumerate(a):
        part_a.setdefault(lab, set()).add(v)
    part_b = {}
    for v, lab in enumerate(b):
        part_b.setdefault(lab, set()).add(v)
    assert sorted(map(sorted, part_a.values())) == sorted(
        map(sorted, part_b.values())
    )


def test_lacc_path_and_cliques(rng):
    from combblas_tpu.models.cc import lacc, num_components

    grid = Grid.make(2, 2)
    n = 24
    d = np.zeros((n, n), np.float32)
    for i in range(9):  # path 0..9
        d[i, i + 1] = d[i + 1, i] = 1
    d[10:16, 10:16] = 1  # clique
    np.fill_diagonal(d, 0)
    A = SpParMat.from_dense(grid, d)
    labels, it = lacc(A)
    lab = labels.to_global()
    assert len(set(lab[:10])) == 1
    assert len(set(lab[10:16])) == 1
    assert num_components(labels) == 2 + (n - 16)


def test_sssp_batch_matches_single(rng):
    """Multi-source Bellman-Ford lanes == per-source runs."""
    import jax.numpy as jnp

    from combblas_tpu.models.sssp import sssp, sssp_batch
    from combblas_tpu.parallel.ellmat import EllParMat
    from combblas_tpu.parallel.spmat import SpParMat

    grid = Grid.make(2, 2)
    n = 48
    d = (rng.random((n, n)) < 0.1).astype(np.float32) * (
        0.1 + rng.random((n, n)).astype(np.float32)
    )
    np.fill_diagonal(d, 0)
    r, c = np.nonzero(d)
    A = SpParMat.from_global_coo(grid, r, c, d[r, c], n, n)
    E = EllParMat.from_host_coo(
        grid, r.astype(np.int64), c.astype(np.int64),
        d[r, c].astype(np.float32), n, n,
    )
    srcs = [0, 5, 17]
    db, _, _ = sssp_batch(E, jnp.asarray(srcs, jnp.int32))
    got = db.to_global()
    for w, s in enumerate(srcs):
        dist, _ = sssp(A, s)
        np.testing.assert_allclose(got[:, w], dist.to_global(), rtol=1e-5)


def test_triangle_count_dense_kernel(rng):
    """Round-4 one-launch MXU TC must match the sparse path."""
    from combblas_tpu.models.tc import triangle_count

    grid = Grid.make(1, 1)
    n = 40
    d = (rng.random((n, n)) < 0.25).astype(np.float32)
    d = np.maximum(d, d.T)
    np.fill_diagonal(d, 0.0)
    A = SpParMat.from_dense(grid, d)
    want = triangle_count(A, kernel="sparse")
    got = triangle_count(A, kernel="dense")
    assert got == want


def test_triangle_count_edge_harvest_kernel(rng):
    """The edge-harvest TC (two row gathers an edge) must match the
    sparse and dense paths, including when the edge count doesn't
    divide the scan chunk."""
    from combblas_tpu.models.tc import triangle_count

    grid = Grid.make(1, 1)
    n = 48
    d = (rng.random((n, n)) < 0.3).astype(np.float32)
    d = np.maximum(d, d.T)
    np.fill_diagonal(d, 0.0)
    A = SpParMat.from_dense(grid, d)
    want = triangle_count(A, kernel="sparse")
    assert triangle_count(A, kernel="edgeharvest") == want
    assert triangle_count(A, kernel="dense") == want


def test_triangle_count_edge_harvest_duplicates(rng):
    """The edge harvest must survive duplicate COO entries: a
    double-added bit would carry into the next bit and corrupt the
    adjacency, and a duplicated edge would walk its common neighbors
    twice and double-count 3T (ADVICE r5) — dedup happens on device."""
    from combblas_tpu.models.tc import triangle_count

    grid = Grid.make(1, 1)
    n = 40
    d = (rng.random((n, n)) < 0.3).astype(np.float32)
    d = np.maximum(d, d.T)
    np.fill_diagonal(d, 0.0)
    r, c = np.nonzero(d)
    # duplicate a third of the entries (and one entry three times)
    dup = np.arange(0, len(r), 3)
    r2 = np.concatenate([r, r[dup], r[:1], r[:1]])
    c2 = np.concatenate([c, c[dup], c[:1], c[:1]])
    A = SpParMat.from_global_coo(
        grid, r2, c2, np.ones(len(r2), np.float32), n, n
    )
    want = triangle_count(
        SpParMat.from_global_coo(
            grid, r, c, np.ones(len(r), np.float32), n, n
        ),
        kernel="sparse",
    )
    assert triangle_count(A, kernel="edgeharvest") == want


@pytest.mark.parametrize("kernel", ["edgeharvest_bf16", "bits", ""])
def test_triangle_count_refuses_a_kernel_it_does_not_have(kernel):
    """An unknown ``kernel`` names the four there are (at PR 42 it fell
    through to the masked product, and ``edgeharvest_bf16`` ran a
    kernel no rule picked)."""
    from combblas_tpu.models.tc import triangle_count

    A = SpParMat.from_global_coo(
        Grid.make(1, 1), np.array([1, 0]), np.array([0, 1]),
        np.ones(2, np.float32), 4, 4,
    )
    with pytest.raises(
        ValueError, match="auto, dense, edgeharvest, sparse"
    ):
        triangle_count(A, kernel=kernel)
