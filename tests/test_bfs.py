"""Distributed SpMV and end-to-end BFS on virtual meshes.

The reference's BFS drivers self-check via traversal stats on generated
R-MATs (SURVEY.md §4.3); we go further and validate the whole parent tree
against a host BFS (the Graph500 verify.c checks the reference never wires
in).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu import MIN_PLUS, PLUS_TIMES, SELECT2ND_MAX
from combblas_tpu.models.bfs import bfs, traversed_edges, validate_bfs_tree
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.parallel.spmv import dist_spmv
from combblas_tpu.parallel.vec import DistVec
from combblas_tpu.utils.rmat import rmat_edges, rmat_symmetric_coo
from conftest import random_dense

GRIDS = [(1, 1), (2, 2), (2, 4)]


@pytest.fixture(params=GRIDS, ids=[f"{a}x{b}" for a, b in GRIDS])
def grid(request):
    return Grid.make(*request.param)


def test_dist_spmv_plus_times(grid, rng):
    d = random_dense(rng, 22, 17)
    A = SpParMat.from_dense(grid, d)
    x = rng.random(17).astype(np.float32)
    y = dist_spmv(PLUS_TIMES, A, DistVec.from_global(grid, x))
    assert y.align == "row"
    np.testing.assert_allclose(y.to_global(), d @ x, rtol=1e-5)


def test_dist_spmv_min_plus(grid, rng):
    d = random_dense(rng, 11, 11, 0.4)
    A = SpParMat.from_dense(grid, d)
    x = rng.random(11).astype(np.float32)
    y = dist_spmv(MIN_PLUS, A, DistVec.from_global(grid, x))
    expect = np.where(d != 0, d + x[None, :], np.inf).min(axis=1)
    got = y.to_global()
    mask = ~np.isinf(expect)
    np.testing.assert_allclose(got[mask], expect[mask], rtol=1e-6)
    assert np.all(np.isinf(got[~mask]))


def test_dist_spmv_jitted(grid, rng):
    d = random_dense(rng, 16, 16)
    A = SpParMat.from_dense(grid, d)
    x = DistVec.from_global(grid, rng.random(16).astype(np.float32))
    f = jax.jit(lambda A, x: dist_spmv(PLUS_TIMES, A, x))
    np.testing.assert_allclose(f(A, x).to_global(), d @ x.to_global(), rtol=1e-5)


def test_rmat_generator_deterministic():
    key = jax.random.key(7)
    s1, d1 = rmat_edges(key, 8, 1000)
    s2, d2 = rmat_edges(key, 8, 1000)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert np.asarray(s1).max() < 256 and np.asarray(d1).min() >= 0
    # skewed degree distribution: top vertex should have far more than mean
    deg = np.bincount(np.asarray(s1), minlength=256)
    assert deg.max() > 4 * deg.mean()


def test_bfs_small_path_graph(grid):
    # path 0-1-2-3-4 plus isolated 5,6
    n = 7
    d = np.zeros((n, n), np.float32)
    for u, v in [(0, 1), (1, 2), (2, 3), (3, 4)]:
        d[u, v] = d[v, u] = 1
    A = SpParMat.from_dense(grid, d)
    parents, levels, niter = bfs(A, 0)
    np.testing.assert_array_equal(levels.to_global(), [0, 1, 2, 3, 4, -1, -1])
    assert validate_bfs_tree(d, 0, parents.to_global(), levels.to_global()) == []
    assert int(niter) == 5  # 4 expanding levels + 1 empty-frontier detection


def test_bfs_rmat(grid):
    rows, cols = rmat_symmetric_coo(jax.random.key(3), scale=7, edgefactor=8)
    n = 1 << 7
    A = SpParMat.from_global_coo(
        grid, rows, cols, np.ones(len(rows), np.float32), n, n,
        dedup_sr=PLUS_TIMES,
    )
    d = A.to_dense()
    src = int(np.argmax((d != 0).sum(axis=0)))  # highest-degree vertex
    parents, levels, _ = bfs(A, src)
    errs = validate_bfs_tree(d, src, parents.to_global(), levels.to_global())
    assert errs == [], errs[:5]
    te = int(traversed_edges(A, parents))
    assert te > 0


def test_bfs_matches_across_grids():
    rows, cols = rmat_symmetric_coo(jax.random.key(5), scale=6, edgefactor=8)
    n = 64
    levels_by_grid = []
    for g in GRIDS:
        grid = Grid.make(*g)
        A = SpParMat.from_global_coo(
            grid, rows, cols, np.ones(len(rows), np.float32), n, n,
            dedup_sr=PLUS_TIMES,
        )
        _, levels, _ = bfs(A, 0)
        levels_by_grid.append(levels.to_global())
    for lv in levels_by_grid[1:]:
        np.testing.assert_array_equal(lv, levels_by_grid[0])


@pytest.mark.parametrize("shape", [
    (1, 1),
    # (2,2) is slow-lane (round 17, tier-1 budget): the batched
    # lanes are grid-independent mechanics and (2,4) keeps the
    # tier-1-mesh representative
    pytest.param((2, 2), marks=pytest.mark.slow),
    (2, 4),
])
def test_bfs_batch_matches_single(shape):
    """Multi-source batched BFS (one [n, W] frontier matrix) must produce,
    per lane, exactly the trees/levels of the single-root driver."""
    from combblas_tpu.models.bfs import bfs_batch
    from combblas_tpu.parallel.ellmat import EllParMat

    rows, cols = rmat_symmetric_coo(jax.random.key(11), 8, 6)
    n = 1 << 8
    grid = Grid.make(*shape)
    E = EllParMat.from_host_coo(
        grid, np.asarray(rows), np.asarray(cols),
        np.ones(len(rows), np.float32), n, n,
    )
    deg = np.bincount(np.asarray(rows), minlength=n)
    srcs = np.flatnonzero(deg > 0)[[0, 3, 17, 29]].astype(np.int32)
    pb, lb, it = bfs_batch(E, jnp.asarray(srcs))
    P = pb.to_global()  # [n, W]
    L = lb.to_global()
    assert P.shape == (n, len(srcs))
    for k, s in enumerate(srcs):
        p1, l1, _ = bfs(E, int(s))
        np.testing.assert_array_equal(L[:, k], l1.to_global())
        # parents may differ in ties only if semiring add differed; the same
        # SELECT2ND_MAX tie-break applies in both drivers
        np.testing.assert_array_equal(P[:, k], p1.to_global())


def test_batch_traversed_edges_matches_host():
    from combblas_tpu.models.bfs import batch_traversed_edges, bfs_batch
    from combblas_tpu.parallel.ellmat import EllParMat

    rows, cols = rmat_symmetric_coo(jax.random.key(5), 7, 8)
    n = 1 << 7
    grid = Grid.make(2, 2)
    E = EllParMat.from_host_coo(
        grid, np.asarray(rows), np.asarray(cols),
        np.ones(len(rows), np.float32), n, n,
    )
    deg = np.bincount(np.asarray(rows), minlength=n)
    srcs = np.flatnonzero(deg > 0)[[1, 5]].astype(np.int32)
    pb, _, _ = bfs_batch(E, jnp.asarray(srcs))
    lr = grid.local_rows(n)
    degb = jnp.asarray(
        np.pad(deg, (0, lr * grid.pr - n)).reshape(grid.pr, lr), jnp.int32
    )
    te = np.asarray(batch_traversed_edges(degb, pb))
    P = pb.to_global()
    for k in range(len(srcs)):
        expect = int(deg[P[:, k] >= 0].sum()) // 2
        assert te[k] == expect


@pytest.mark.parametrize("shape", [
    (1, 1),
    # (2,2) is slow-lane (round 17, tier-1 budget): (1,1) covers
    # the compact-lane mechanics, (2,4) the tier-1 mesh
    pytest.param((2, 2), marks=pytest.mark.slow),
    (2, 4),
])
def test_bfs_batch_compact_matches(shape):
    """Level-compressed batched BFS: identical levels to bfs_batch, and a
    valid BFS tree per lane (parents reconstructed post-hoc are any valid
    tree, so trees are validated, not compared)."""
    from combblas_tpu.models.bfs import bfs_batch, bfs_batch_compact
    from combblas_tpu.parallel.ellmat import EllParMat

    rows, cols = rmat_symmetric_coo(jax.random.key(13), 8, 6)
    n = 1 << 8
    grid = Grid.make(*shape)
    E = EllParMat.from_host_coo(
        grid, np.asarray(rows), np.asarray(cols),
        np.ones(len(rows), np.float32), n, n,
    )
    deg = np.bincount(np.asarray(rows), minlength=n)
    srcs = np.flatnonzero(deg > 0)[[0, 5, 23]].astype(np.int32)
    p1, l1, _ = bfs_batch(E, jnp.asarray(srcs))
    p2, l2, it = bfs_batch_compact(E, jnp.asarray(srcs))
    L1 = l1.to_global()
    L2 = l2.to_global().astype(np.int32)
    np.testing.assert_array_equal(L1, L2)
    # dense adjacency for tree validation
    d = np.zeros((n, n), bool)
    d[np.asarray(rows), np.asarray(cols)] = True
    P2 = p2.to_global()
    from combblas_tpu.models.bfs import validate_bfs_tree

    for k, s in enumerate(srcs):
        assert not validate_bfs_tree(d, int(s), P2[:, k], L2[:, k]), k


def test_bfs_batch_compact_ring_schedule():
    """The carousel (ppermute ring) fold produces identical levels to the
    fused all-reduce on a multi-device grid — the BitMapCarousel schedule
    as a real, testable program (BFSFriends.h:457-560)."""
    from combblas_tpu.models.bfs import bfs_batch_compact
    from combblas_tpu.parallel.ellmat import EllParMat

    rows, cols = rmat_symmetric_coo(jax.random.key(2), 8, 6)
    n = 1 << 8
    grid = Grid.make(2, 4)
    E = EllParMat.from_host_coo(
        grid, np.asarray(rows), np.asarray(cols),
        np.ones(len(rows), np.float32), n, n,
    )
    deg = np.bincount(np.asarray(rows), minlength=n)
    srcs = np.flatnonzero(deg > 0)[[0, 11]].astype(np.int32)
    _, l1, _ = bfs_batch_compact(E, jnp.asarray(srcs))
    _, l2, _ = bfs_batch_compact(E, jnp.asarray(srcs), ring=True)
    np.testing.assert_array_equal(l1.to_global(), l2.to_global())


@pytest.mark.parametrize("shape", [
    (1, 1),
    # the multi-device variant is slow-lane (round 12, tier-1 budget);
    # the diropt union-step's distributed path keeps coverage via
    # test_bfs_diropt and the 1x1 representative here
    pytest.param((2, 2), marks=pytest.mark.slow),
])
def test_bfs_batch_compact_diropt_matches(shape):
    """The union-frontier budgeted sparse regime (on-device lax.cond)
    produces identical levels + valid trees vs the always-dense path."""
    from combblas_tpu.models.bfs import bfs_batch_compact, validate_bfs_tree
    from combblas_tpu.parallel.ellmat import EllParMat, build_csc_companion

    rows, cols = rmat_symmetric_coo(jax.random.key(21), 8, 6)
    n = 1 << 8
    grid = Grid.make(*shape)
    rr, cc = np.asarray(rows), np.asarray(cols)
    E = EllParMat.from_host_coo(
        grid, rr, cc, np.ones(len(rr), np.float32), n, n
    )
    csc = build_csc_companion(grid, rr, cc, n, n)
    deg = np.bincount(rr, minlength=n)
    srcs = np.flatnonzero(deg > 0)[[0, 3]].astype(np.int32)
    _, l0, _ = bfs_batch_compact(E, jnp.asarray(srcs))
    # small budgets: some levels sparse, some dense
    p1, l1, _ = bfs_batch_compact(
        E, jnp.asarray(srcs), csc=csc,
        frontier_capacity=16, edge_capacity=256,
    )
    np.testing.assert_array_equal(l0.to_global(), l1.to_global())
    # generous budgets: everything through the sparse kernel
    p2, l2, _ = bfs_batch_compact(
        E, jnp.asarray(srcs), csc=csc,
        frontier_capacity=n, edge_capacity=4 * len(rr),
    )
    np.testing.assert_array_equal(l0.to_global(), l2.to_global())
    d = np.zeros((n, n), bool)
    d[rr, cc] = True
    for k, s_ in enumerate(srcs):
        assert not validate_bfs_tree(
            d, int(s_), p1.to_global()[:, k],
            l1.to_global().astype(np.int32)[:, k],
        ), k


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
def test_validate_bfs_device(shape, rng):
    """Device-side Graph500 tree validation: clean trees pass, corrupted
    trees are flagged with the right violation class."""
    import dataclasses

    from combblas_tpu.models.bfs import bfs_batch, validate_bfs_device
    from combblas_tpu.parallel.ellmat import EllParMat

    grid = Grid.make(*shape)
    n = 64
    d = rng.random((n, n)) < 0.08
    d = d | d.T
    np.fill_diagonal(d, 0)
    rr, cc = np.nonzero(d)
    E = EllParMat.from_host_coo(
        grid, rr.astype(np.int64), cc.astype(np.int64),
        np.ones(len(rr), np.float32), n, n,
    )
    deg = np.bincount(rr, minlength=n)
    srcs = np.flatnonzero(deg > 0)[[0, 2]].astype(np.int32)
    p, l, _ = bfs_batch(E, jnp.asarray(srcs))
    v = np.asarray(validate_bfs_device(E, p, l))
    assert v.shape == (4, 2)
    assert (v == 0).all(), v

    # corrupt lane 0: point one discovered vertex's parent at a non-neighbor
    pg = p.to_global().copy()
    lg = l.to_global().copy()
    disc = np.flatnonzero((pg[:, 0] >= 0) & (pg[:, 0] != np.arange(n)))
    victim = int(disc[-1])
    non_neighbors = np.flatnonzero(~d[victim])
    bad_parent = int(non_neighbors[0])
    pg[victim, 0] = bad_parent
    from combblas_tpu.parallel.vec import DistMultiVec

    p_bad = DistMultiVec.from_global(grid, pg.astype(np.int32), align="row")
    v2 = np.asarray(validate_bfs_device(E, p_bad, l))
    assert v2[2, 0] > 0  # tree-edge violation in lane 0
    assert (v2[:, 1] == 0).all()  # lane 1 untouched

    # corrupt levels: shift a discovered vertex's level by 2
    lg2 = lg.copy()
    lg2[victim, 0] = lg2[victim, 0] + 2
    l_bad = DistMultiVec.from_global(grid, lg2.astype(np.int32), align="row")
    v3 = np.asarray(validate_bfs_device(E, p, l_bad))
    assert v3[1, 0] > 0 or v3[3, 0] > 0


def _bfs_single_sweep(shape, root_idx, tier_sets):
    """Shared body of the bfs_single agreement tests: run each root
    through each tier config and compare levels + tree validity
    against the reference ``bfs()``."""
    from combblas_tpu.models.bfs import bfs, bfs_single, validate_bfs_tree
    from combblas_tpu.parallel.ellmat import EllParMat, build_csc_companion
    from combblas_tpu.parallel.spmat import SpParMat

    rows, cols = rmat_symmetric_coo(jax.random.key(31), 8, 6)
    n = 1 << 8
    grid = Grid.make(*shape)
    rr, cc = np.asarray(rows), np.asarray(cols)
    E = EllParMat.from_host_coo(
        grid, rr, cc, np.ones(len(rr), np.float32), n, n
    )
    A = SpParMat.from_global_coo(
        grid, rr, cc, np.ones(len(rr), np.float32), n, n
    )
    csc = build_csc_companion(grid, rr, cc, n, n)
    from combblas_tpu.parallel.ellmat import build_csr_companion

    csr = build_csr_companion(grid, rr, cc, n, n)
    deg = np.bincount(rr, minlength=n)
    d = np.zeros((n, n), bool)
    d[rr, cc] = True
    for s in np.flatnonzero(deg > 0)[list(root_idx)]:
        p0, l0, _ = bfs(A, int(s))
        L0 = l0.to_global()
        for tiers in tier_sets:
            p1, l1, _ = bfs_single(E, int(s), csc, csr=csr, tiers=tiers)
            np.testing.assert_array_equal(L0, l1.to_global(), err_msg=str(tiers))
            assert not validate_bfs_tree(
                d, int(s), p1.to_global(), l1.to_global()
            ), tiers


_BFS_SINGLE_N = 1 << 8
_BFS_SINGLE_BIG = (_BFS_SINGLE_N,) * 6
#: The four tier regimes the sweep covers; each DISTINCT tuple traces
#: its own one-launch program, so compiles dominate the test's cost.
_BFS_SINGLE_TIERS = (
    (("td", (1, 0, 0, 0, 0, 0)),),          # forces dense nearly always
    (("td", _BFS_SINGLE_BIG),),             # everything top-down
    (("bu", _BFS_SINGLE_BIG),),             # everything bottom-up
    (("td", (4, 2, 1, 0, 0, 0)), ("bu", (16, 8, 2, 0, 0, 0)),
     ("td", _BFS_SINGLE_BIG)),              # mixed ladder
)


def test_bfs_single_matches():
    """Single-root tiered BFS (the spec's sequential kernel 2), the
    tier-1 representative (round 17, budget): ONE root through the
    two information-densest regimes — the forced-dense config and the
    mixed td/bu/td ladder (which exercises every tier transition plus
    the dense peak in one program).  The full sweep (both roots, all
    four regimes, multi-device grids) runs under ``-m slow``."""
    _bfs_single_sweep(
        (1, 1), [0], (_BFS_SINGLE_TIERS[0], _BFS_SINGLE_TIERS[3])
    )


@pytest.mark.slow
@pytest.mark.parametrize("shape", [(1, 1), (2, 2), (2, 4)])
def test_bfs_single_matches_full_sweep(shape):
    """The exhaustive regime x root x grid sweep (each pure-td and
    pure-bu ladder compiles its own ~10 s program on the 1-core CPU
    mesh; the fast representative above keeps the mixed ladder +
    forced-dense coverage in tier-1)."""
    _bfs_single_sweep(shape, [0, 7], _BFS_SINGLE_TIERS)


def test_single_traversed_edges_matches():
    from combblas_tpu.models.bfs import (
        bfs_single, single_traversed_edges,
    )
    from combblas_tpu.parallel.ellmat import EllParMat, build_csc_companion

    rows, cols = rmat_symmetric_coo(jax.random.key(5), 8, 6)
    n = 1 << 8
    grid = Grid.make(2, 2)
    rr, cc = np.asarray(rows), np.asarray(cols)
    E = EllParMat.from_host_coo(
        grid, rr, cc, np.ones(len(rr), np.float32), n, n
    )
    csc = build_csc_companion(grid, rr, cc, n, n)
    deg = np.bincount(rr, minlength=n)
    s = int(np.flatnonzero(deg > 0)[0])
    p, _, _ = bfs_single(E, s, csc, tiers=(("td", (64, 64, 64, 0, 0, 0)),))
    lr = grid.local_rows(n)
    degb = jnp.asarray(
        np.pad(deg, (0, lr * grid.pr - n)).reshape(grid.pr, lr), jnp.int32
    )
    te = int(np.asarray(single_traversed_edges(degb, p)))
    P = p.to_global()
    assert te == int(deg[P >= 0].sum()) // 2


# --- thin sweeps: a degree class with no active row is skipped (PR 24) ------


def _thin_case(name):
    """``(rows, cols, n, roots, max_k)`` of one case of the thin-sweep
    agreement test: symmetric COO, int32 roots (``PAD_ROOT`` = a pad
    lane)."""
    from combblas_tpu.models import PAD_ROOT

    def rmat(scale, seed):
        r, c = rmat_symmetric_coo(jax.random.key(seed), scale, 8)
        return np.asarray(r), np.asarray(c), 1 << scale

    if name in ("rmat", "pad_lanes", "split_hubs"):
        rows, cols, n = rmat(10, 31)
        roots = np.flatnonzero(np.bincount(rows, minlength=n))[:16]
        if name == "pad_lanes":
            roots = np.concatenate([roots[:5], np.full(11, PAD_ROOT)])
        # max_k 8: a row of degree 9 or more spans several bucket rows,
        # the hubs dozens, all of one class
        return rows, cols, n, roots, (8 if name == "split_hubs" else None)
    if name == "pair_component":
        # lane 0 finishes after one level in a two-vertex component of
        # its own while the others run on
        rows, cols, n = rmat(10, 33)
        deg = np.bincount(rows, minlength=n)
        a, b = np.flatnonzero(deg == 0)[:2]
        rows = np.concatenate([rows, [a, b]])
        cols = np.concatenate([cols, [b, a]])
        roots = np.concatenate([[a], np.flatnonzero(deg)[:7]])
        return rows, cols, n, roots, None
    if name == "path":
        # every level's frontier is one or two vertices a lane
        n = 96
        i = np.arange(n - 1)
        rows, cols = np.concatenate([i, i + 1]), np.concatenate([i + 1, i])
        return rows, cols, n, np.array([0, 40, 95, 7]), None
    raise ValueError(name)


@pytest.mark.parametrize("case,shape", [
    ("rmat", (1, 1)),
    ("rmat", (2, 2)),
    ("rmat", (4, 2)),
    ("pad_lanes", (1, 1)),
    ("pad_lanes", (2, 2)),
    ("pair_component", (1, 1)),
    ("pair_component", (4, 2)),
    ("path", (1, 1)),
    ("path", (2, 2)),
    ("split_hubs", (1, 1)),
    ("split_hubs", (4, 2)),
])
def test_thin_sweeps_match_all_dense(case, shape, all_dense_sweeps):
    """Both batch programs return bit for bit the parents, levels and
    level count of the all-dense sweep when every degree class decides
    each level whether it has anything to do, and some had not."""
    from combblas_tpu.models.bfs import (
        _bfs_batch_tallied, bfs_batch_compact,
    )
    from combblas_tpu.parallel.ellmat import EllParMat

    rows, cols, n, roots, max_k = _thin_case(case)
    E = EllParMat.from_host_coo(
        Grid.make(*shape), rows, cols, np.ones(len(rows), np.float32),
        n, n, max_k=max_k,
    )
    roots = jnp.asarray(roots, jnp.int32)

    def run():
        served = jax.jit(
            lambda E, r: _bfs_batch_tallied(
                E, r, None, True)[:4]
        )(E, roots)
        p, l, niter = bfs_batch_compact(E, roots)
        return [np.asarray(a) for a in (*served, p.blocks, l.blocks, niter)]

    all_dense_sweeps(True)
    dense = run()
    all_dense_sweeps(False)
    thin = run()
    assert not dense[3].any()  # no class chose, no tally
    # one choice a tile, class and level
    assert thin[3].shape == (*shape, len(E.buckets), 2)
    assert (thin[3].sum(axis=-1) == int(thin[2])).all()
    assert thin[3][..., 1].sum() > 0, thin[3]
    for a, b in zip(dense[:3] + dense[4:], thin[:3] + thin[4:]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("sr_name", ["min_plus", "select2nd_max"])
def test_masked_multi_sweep_is_exact_for_any_semiring(
        sr_name, all_dense_sweeps):
    """The kernel alone: with dead lanes, partly active rows and a weighted
    matrix, the thin masked sweep equals the unmasked product with the
    mask applied after."""
    from combblas_tpu.parallel.ellmat import (
        EllParMat, dist_spmv_ell_masked_multi, dist_spmv_ell_multi,
    )
    from combblas_tpu.parallel.vec import DistMultiVec

    sr = {"min_plus": MIN_PLUS, "select2nd_max": SELECT2ND_MAX}[sr_name]
    rows, cols, n, _, _ = _thin_case("rmat")
    grid = Grid.make(2, 2)
    rng = np.random.default_rng(5)
    E = EllParMat.from_host_coo(
        grid, rows, cols, rng.integers(1, 9, len(rows)).astype(np.float32),
        n, n,
    )
    dtype = np.float32 if sr_name == "min_plus" else np.int32
    zero = np.asarray(sr.zero(dtype))
    x = np.full((n, 8), zero, dtype)
    live = rng.random((n, 8)) < 0.02
    live[:, [1, 6]] = False  # two dead lanes
    x[live] = rng.integers(0, 50, live.sum())
    mask = rng.random((n, 8)) < 0.05
    mask[:, 1] = True  # rows active in a dead lane only stay unswept
    X = DistMultiVec.from_global(grid, x, align="col")
    M = DistMultiVec.from_global(grid, mask, align="row")
    want = np.where(mask, dist_spmv_ell_multi(sr, E, X).to_global(), zero)
    for on in (True, False):
        all_dense_sweeps(on)
        got = dist_spmv_ell_masked_multi(sr, E, X, M).to_global()
        np.testing.assert_array_equal(got, want)


def test_sweep_tally_on_a_path():
    """On a path most levels are thin: the tally counts one choice per
    class and level, and not all dense."""
    from combblas_tpu.models.bfs import _bfs_batch_tallied
    from combblas_tpu.parallel.ellmat import SWEEP_MODES, EllParMat

    rows, cols, n, roots, _ = _thin_case("path")
    E = EllParMat.from_host_coo(
        Grid.make(1, 1), rows, cols, np.ones(len(rows), np.float32), n, n
    )
    _, _, niter, tally, push = jax.jit(
        lambda E, r: _bfs_batch_tallied(E, r, None, True)
    )(E, jnp.asarray(roots, jnp.int32))
    assert push is None  # no companion handed in: no push in the program
    tally = np.asarray(tally)
    assert SWEEP_MODES == ("dense", "skipped")
    assert len(E.buckets) == 2  # the two ends, and the rest
    assert tally.shape == (1, 1, 2, 2)  # a tile, by class and mode
    assert tally[..., 1].sum() > 0
    assert tally.sum(axis=-1).tolist() == [[[int(niter)] * 2]]


@pytest.mark.parametrize("busy,swept", [
    ((), 0),            # nothing busy: every class skipped
    ((0,), 3),          # the narrow tier: 1/32 of the slots
    ((1, 2), 3),
    ((3,), 7),          # one row past it: the tier that ends at a half
    ((1, 4, 6), 7),
    ((7,), 8),          # the widest class: the whole sweep
    ((0, 7), 8),
])
def test_tiered_idle_sweeps_the_tier_of_the_widest_busy_class(busy, swept):
    """The W=256 level kernel's choice is a function of one number: it
    sweeps a prefix of the classes that ends at a tier and holds every
    busy class, whichever narrower classes are idle."""
    from combblas_tpu.parallel.ellmat import SWEEP_TIERS, _tiered_idle

    assert SWEEP_TIERS == (1 / 32, 1 / 2)
    # running slot shares 1/128, 2/128, 4/128 = 1/32, ..., 64/128, 1
    slots = [1, 1, 2, 4, 8, 16, 32, 64]
    buckets = [(np.zeros((s, 1), np.int32), None, None) for s in slots]
    idle = [jnp.asarray(i not in busy) for i in range(len(slots))]
    got = [bool(v) for v in _tiered_idle(buckets, idle)]
    assert got == [i >= swept for i in range(len(slots))]
    assert not any(got[i] for i in busy)


def test_levels_step_holds_a_choice_per_class():
    """``_ell_levels_step`` traces one ``cond`` per degree class (the
    tiers change what each is told, not how many there are)."""
    from combblas_tpu.parallel.ellmat import EllParMat, _ell_levels_step

    rows, cols, n, _, _ = _thin_case("rmat")
    E = EllParMat.from_host_coo(
        Grid.make(1, 1), rows, cols, np.ones(len(rows), np.float32), n, n
    )
    x8 = jnp.zeros((1, E.local_cols, 4), jnp.int8)
    u8 = jnp.ones((1, E.local_rows, 4), jnp.int8)
    text = str(jax.make_jaxpr(lambda *a: _ell_levels_step(*a))(E, x8, u8))
    assert text.count(" cond[") == len(E.buckets)
