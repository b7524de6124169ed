"""Local ESC SpGEMM and distributed SUMMA vs dense numpy products.

Mirrors the reference's MultTest golden-product pattern
(ReleaseTests/MultTest.cpp:122-234) with generated inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu import MIN_PLUS, OR_AND, PLUS_TIMES, SpTuples
from combblas_tpu.ops.compressed import CSR
from combblas_tpu.ops.spgemm import expand, flops, local_spgemm
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spgemm import spgemm, summa_capacities, summa_spgemm
from combblas_tpu.parallel.spmat import SpParMat
from conftest import random_dense


def test_local_flops(rng):
    da = random_dense(rng, 9, 7, 0.4)
    db = random_dense(rng, 7, 11, 0.4)
    a = SpTuples.from_dense(da, capacity=64)
    b = CSR.from_tuples(SpTuples.from_dense(db, capacity=64))
    expect = sum(
        int((db[k] != 0).sum()) for i, k in zip(*np.nonzero(da))
    )
    assert int(flops(a, b)) == expect


def test_local_spgemm_plus_times(rng):
    da = random_dense(rng, 13, 9, 0.35)
    db = random_dense(rng, 9, 10, 0.35)
    a = SpTuples.from_dense(da, capacity=128)
    b = CSR.from_tuples(SpTuples.from_dense(db, capacity=128))
    from combblas_tpu.ops.spgemm import flops_padded

    fl = int(flops(a, b))
    flp = int(flops_padded(a, b))
    c = local_spgemm(PLUS_TIMES, a, b, flop_capacity=max(flp, 1), out_capacity=max(fl, 1))
    np.testing.assert_allclose(np.asarray(c.to_dense()), da @ db, rtol=1e-5, atol=1e-6)


def test_local_spgemm_min_plus(rng):
    da = random_dense(rng, 6, 6, 0.5)
    db = random_dense(rng, 6, 6, 0.5)
    a = SpTuples.from_dense(da, capacity=36)
    b = CSR.from_tuples(SpTuples.from_dense(db, capacity=36))
    from combblas_tpu.ops.spgemm import flops_padded

    c = local_spgemm(
        MIN_PLUS, a, b,
        flop_capacity=int(flops_padded(a, b)), out_capacity=64,
    )
    expect = np.full((6, 6), np.inf, np.float32)
    for i in range(6):
        for j in range(6):
            for k in range(6):
                if da[i, k] and db[k, j]:
                    expect[i, j] = min(expect[i, j], da[i, k] + db[k, j])
    got = np.asarray(c.to_dense(MIN_PLUS))
    mask = ~np.isinf(expect)
    np.testing.assert_allclose(got[mask], expect[mask], rtol=1e-6)


@pytest.mark.parametrize("p", [1, 2])
@pytest.mark.parametrize("ring", [False, True])
def test_summa_vs_dense(p, ring, rng):
    grid = Grid.make(p, p)
    da = random_dense(rng, 21, 17, 0.25)
    db = random_dense(rng, 17, 19, 0.25)
    A = SpParMat.from_dense(grid, da)
    B = SpParMat.from_dense(grid, db)
    flop_cap, out_cap = summa_capacities(A, B)
    C = summa_spgemm(
        PLUS_TIMES, A, B,
        flop_capacity=flop_cap, out_capacity=out_cap, ring=ring,
    )
    np.testing.assert_allclose(C.to_dense(), da @ db, rtol=1e-5, atol=1e-6)


def test_summa_boolean_reachability(rng):
    grid = Grid.make(2, 2)
    da = (random_dense(rng, 16, 16, 0.15) != 0)
    A = SpParMat.from_dense(grid, da.astype(np.float32))
    A2 = spgemm(OR_AND, A.apply(lambda v: v != 0), A.apply(lambda v: v != 0))
    expect = (da.astype(np.int32) @ da.astype(np.int32)) > 0
    np.testing.assert_array_equal(A2.to_dense().astype(bool), expect)


def test_summa_square_rmat(rng):
    from combblas_tpu.utils.rmat import rmat_symmetric_coo

    rows, cols = rmat_symmetric_coo(jax.random.key(11), scale=6, edgefactor=6)
    n = 64
    grid = Grid.make(2, 2)
    A = SpParMat.from_global_coo(
        grid, rows, cols, np.ones(len(rows), np.float32), n, n,
        dedup_sr=PLUS_TIMES,
    )
    d = A.to_dense()
    C = spgemm(PLUS_TIMES, A, A)
    np.testing.assert_allclose(C.to_dense(), d @ d, rtol=1e-4, atol=1e-5)
    # jitted with static capacities
    flop_cap, out_cap = summa_capacities(A, A)
    f = jax.jit(
        lambda A, B: summa_spgemm(
            PLUS_TIMES, A, B, flop_capacity=flop_cap, out_capacity=out_cap
        )
    )
    np.testing.assert_allclose(f(A, A).to_dense(), d @ d, rtol=1e-4, atol=1e-5)


def test_summa_rect_matrices_nonuniform(rng):
    # shapes that don't divide the grid evenly
    grid = Grid.make(2, 2)
    da = random_dense(rng, 23, 15, 0.3)
    db = random_dense(rng, 15, 27, 0.3)
    A = SpParMat.from_dense(grid, da)
    B = SpParMat.from_dense(grid, db)
    C = spgemm(PLUS_TIMES, A, B)
    np.testing.assert_allclose(C.to_dense(), da @ db, rtol=1e-5, atol=1e-6)


def test_spgemm_scan_matches_summa(rng):
    """Output-bounded scanned SUMMA == the unphased product."""
    from combblas_tpu.parallel.spgemm import spgemm_scan

    grid = Grid.make(2, 2)
    n = 40
    d = (rng.random((n, n)) < 0.15).astype(np.float32)
    A = SpParMat.from_dense(grid, d)
    C1 = spgemm(PLUS_TIMES, A, A)
    C2 = spgemm_scan(PLUS_TIMES, A, A)
    np.testing.assert_allclose(C2.to_dense(), d @ d, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(C2.to_dense(), C1.to_dense(), rtol=1e-6)


def test_spgemm_scan_ring_matches(rng):
    from combblas_tpu.parallel.spgemm import spgemm_scan

    grid = Grid.make(2, 2)
    n = 32
    d = (rng.random((n, n)) < 0.2).astype(np.float32)
    A = SpParMat.from_dense(grid, d)
    C = spgemm_scan(PLUS_TIMES, A, A, ring=True)
    np.testing.assert_allclose(C.to_dense(), d @ d, rtol=1e-5, atol=1e-6)


def test_spgemm_scan_overflow_retry(rng):
    """A deliberately tiny initial out_capacity must be corrected by the
    exact distinct-key count (the estimateNNZ_Hash role) via retry."""
    from combblas_tpu.parallel.spgemm import spgemm_scan, summa_spgemm_scan, summa_capacities

    grid = Grid.make(2, 2)
    n = 32
    d = (rng.random((n, n)) < 0.3).astype(np.float32)
    A = SpParMat.from_dense(grid, d)
    # direct call underreports capacity -> overflow flagged, result truncated
    fcap, _ = summa_capacities(A, A)
    C, overflow = summa_spgemm_scan(
        PLUS_TIMES, A, A, flop_capacity=fcap, out_capacity=4
    )
    assert int(overflow) > 0
    # driver retries to exactness
    C2 = spgemm_scan(PLUS_TIMES, A, A, out_capacity=4)
    np.testing.assert_allclose(C2.to_dense(), d @ d, rtol=1e-5, atol=1e-6)


def test_spgemm_scan_memory_bounded(rng):
    """The scanned variant's compiled peak memory must undercut the
    all-stages-live variant when flops >> nnz_out (the MCL A-squared
    regime) — the round-1 'ESC peak memory scales with flops' weakness."""
    import jax

    from combblas_tpu.parallel.spgemm import summa_spgemm, summa_spgemm_scan

    grid = Grid.make(2, 2)
    n = 64
    # dense-ish columns -> high collision: flops ~ nnz^2/n >> nnz_out <= n^2
    d = (rng.random((n, n)) < 0.5).astype(np.float32)
    A = SpParMat.from_dense(grid, d)
    fcap, ocap = 1 << 17, 1 << 10  # flops-shaped vs output-shaped
    lowered_old = jax.jit(
        lambda a: summa_spgemm(
            PLUS_TIMES, a, a, flop_capacity=fcap, out_capacity=ocap
        )
    ).lower(A)
    lowered_new = jax.jit(
        lambda a: summa_spgemm_scan(
            PLUS_TIMES, a, a, flop_capacity=fcap, out_capacity=ocap
        )
    ).lower(A)
    mem_old = lowered_old.compile().memory_analysis()
    mem_new = lowered_new.compile().memory_analysis()
    assert mem_new.temp_size_in_bytes < mem_old.temp_size_in_bytes, (
        mem_new.temp_size_in_bytes, mem_old.temp_size_in_bytes,
    )


@pytest.mark.parametrize("srname", [
    "plus_times", "min_plus",
    # max_min rides the slow lane (tier-1 870 s budget, round 12): the
    # same dense-kernel path as min_plus, which stays as the tropical
    # tier-1 representative
    pytest.param("max_min", marks=pytest.mark.slow),
])
def test_spgemm_mxu_matches_dense(rng, srname):
    """Dense-block MXU SUMMA == reference product for every dense-kernel
    semiring (Pallas kernel in interpret mode on CPU)."""
    from combblas_tpu import MAX_MIN
    from combblas_tpu.parallel.spgemm import spgemm_auto

    sr = {"plus_times": PLUS_TIMES, "min_plus": MIN_PLUS,
          "max_min": MAX_MIN}[srname]
    grid = Grid.make(2, 2)
    n = 48
    d = (rng.random((n, n)) < 0.2).astype(np.float32) * (
        1 + rng.random((n, n)).astype(np.float32)
    )
    A = SpParMat.from_dense(grid, d)
    C = spgemm_auto(sr, A, A, interpret=True)
    got = C.to_dense()
    if srname == "plus_times":
        np.testing.assert_allclose(got, d @ d, rtol=1e-5, atol=1e-6)
    else:
        # the ESC kernel is the independently-tested reference for the
        # tropical semirings
        want = spgemm(sr, A, A).to_dense()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_spgemm_mxu_overflow_retry(rng):
    from combblas_tpu.parallel.spgemm import spgemm_auto

    grid = Grid.make(2, 2)
    n = 32
    d = (rng.random((n, n)) < 0.3).astype(np.float32)
    A = SpParMat.from_dense(grid, d)
    C = spgemm_auto(PLUS_TIMES, A, A, out_capacity=4, interpret=True)
    np.testing.assert_allclose(C.to_dense(), d @ d, rtol=1e-5, atol=1e-6)


def test_densify_sparsify_roundtrip(rng):
    from combblas_tpu import SpTuples
    from combblas_tpu.ops.spgemm import densify, sparsify

    d = (rng.random((20, 36)) < 0.25).astype(np.float32)
    t = SpTuples.from_dense(d, capacity=512)
    dense = densify(t, 128, 128, 0.0)
    np.testing.assert_allclose(np.asarray(dense)[:20, :36], d)
    back, total = sparsify(dense, 0.0, 20, 36, 512)
    assert int(total) == int((d != 0).sum())
    got = np.zeros_like(d)
    r, c, v = np.asarray(back.rows), np.asarray(back.cols), np.asarray(back.vals)
    m = r < 20
    got[r[m], c[m]] = v[m]
    np.testing.assert_allclose(got, d)


@pytest.mark.parametrize("mode", ["bf16", "bf16x3"])
def test_spgemm_mxu_precision_modes(rng, mode):
    """bf16 is EXACT on 0/1 inputs (counts < 2^24); bf16x3 split-float is
    f32-grade on general values (round-4 _mxu_dot modes)."""
    from combblas_tpu.parallel.spgemm import spgemm_auto

    grid = Grid.make(2, 2)
    n = 48
    if mode == "bf16":
        d = (rng.random((n, n)) < 0.2).astype(np.float32)
    else:
        d = random_dense(rng, n, n, 0.2)
    A = SpParMat.from_dense(grid, d)
    C = spgemm_auto(PLUS_TIMES, A, A, mode=mode, interpret=True)
    got = np.asarray(C.to_dense())
    want = d @ d
    if mode == "bf16":
        np.testing.assert_array_equal(got, want)  # exact
    else:
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("density", [0.0, 0.05, 0.5, 1.0])
@pytest.mark.parametrize("truncate", [False, True])
@pytest.mark.parametrize("pad", [False, True])
@pytest.mark.parametrize("zero", [0.0, float("inf")])
def test_sparsify_windowed_direct(rng, density, truncate, pad, zero):
    """Direct unit coverage of the production extraction kernel
    (ADVICE r4: it replaced `sparsify` on the MXU SpGEMM / dense-MCL
    paths with only indirect test coverage): density x truncation x
    padded dims x non-zero semiring zero, checked against np.nonzero."""
    from combblas_tpu.ops.spgemm import sparsify_windowed

    R, C = 32, 128  # ncell 4096 = 32 chunks
    nrows, ncols = (27, 99) if pad else (R, C)
    x = np.full((R, C), zero, np.float32)
    m = rng.random((R, C)) < density
    m[nrows:, :] = False
    m[:, ncols:] = False
    x[m] = rng.integers(1, 50, (R, C)).astype(np.float32)[m]
    n_ref = int(m.sum())
    cap = max(n_ref // 2, 8) if truncate else n_ref + 32
    t, total = sparsify_windowed(jnp.asarray(x), zero, nrows, ncols, cap)
    assert int(total) == n_ref  # exact pre-truncation count
    r = np.asarray(t.rows)
    c = np.asarray(t.cols)
    v = np.asarray(t.vals)
    live = (r < nrows) & (np.arange(len(r)) < int(t.nnz))
    assert int(t.nnz) == min(n_ref, cap)
    # every surfaced entry is a real nonzero with the right value
    assert np.all(x[r[live], c[live]] != zero)
    np.testing.assert_array_equal(v[live], x[r[live], c[live]])
    # row-major sorted prefix of the true nonzero set
    flat_got = r[live].astype(np.int64) * C + c[live]
    rr, cc = np.nonzero(m)
    flat_ref = np.sort(rr.astype(np.int64) * C + cc)
    np.testing.assert_array_equal(flat_got, flat_ref[: len(flat_got)])


def _window(name):
    """A [16, 32] window by name: (dense, nrows, ncols)."""
    R, C = 16, 32
    x = np.zeros((R, C), np.float32)
    vals = (np.arange(R * C, dtype=np.float32).reshape(R, C) % 37) + 1
    sparse = (np.arange(R * C).reshape(R, C) * 7) % 11 == 0
    nrows, ncols = R, C
    if name == "full":
        x[:] = vals
    elif name == "one_full_row":
        x[sparse] = vals[sparse]
        x[5] = vals[5]
    elif name == "empty_group_between":
        # rows 4..11 hold nothing: under 4 rows a group, two empty
        # groups between two that hold some
        x[sparse] = vals[sparse]
        x[4:12] = 0
    elif name == "short":
        # set cells past nrows / ncols must not surface
        x[:] = vals
        nrows, ncols = 13, 27
    else:
        assert name == "empty", name
    return x, nrows, ncols


@pytest.mark.parametrize("window", [
    "empty", "full", "one_full_row", "empty_group_between", "short"])
@pytest.mark.parametrize("capacity", ["below", "exact", "above_cells"])
@pytest.mark.parametrize("group_cells", [1, 128, 16 * 32 - 1, 16 * 32])
def test_sparsify_windowed_by_row_groups(
        monkeypatch, window, capacity, group_cells):
    """The grouped extraction (a window of more than
    ``SPARSIFY_GROUP_CELLS`` cells: one sort a group of rows, prefixes
    laid end to end) held to ``numpy.nonzero`` on coordinates, values,
    ``nnz``, ``total`` and the padding, from one row a group (``g = 1``)
    to the whole window (``g = R``, today's flat sort)."""
    from combblas_tpu.ops import spgemm as ops

    monkeypatch.setattr(ops, "SPARSIFY_GROUP_CELLS", group_cells)
    x, nrows, ncols = _window(window)
    R, C = x.shape
    assert ops.sparsify_groups(R, C) == {
        1: 16, 128: 4, R * C - 1: 2, R * C: 1}[group_cells]
    kept = x.copy()
    kept[nrows:] = 0
    kept[:, ncols:] = 0
    r, c = np.nonzero(kept)
    cap = {"below": max(len(r) // 2, 1), "exact": max(len(r), 1),
           "above_cells": R * C + 9}[capacity]
    # jitted afresh: the grain is read at trace time
    t, total = jax.jit(
        lambda d: ops.sparsify_windowed(d, 0.0, nrows, ncols, cap))(
        jnp.asarray(x))
    k = min(len(r), cap)
    assert int(total) == len(r) and int(t.nnz) == k
    assert t.rows.shape == t.cols.shape == t.vals.shape == (cap,)
    np.testing.assert_array_equal(np.asarray(t.rows[:k]), r[:k])
    np.testing.assert_array_equal(np.asarray(t.cols[:k]), c[:k])
    np.testing.assert_array_equal(np.asarray(t.vals[:k]), kept[r[:k], c[:k]])
    assert (np.asarray(t.rows[k:]) == nrows).all()
    assert (np.asarray(t.cols[k:]) == ncols).all()
    assert (np.asarray(t.vals[k:]) == 0).all()


@pytest.mark.parametrize("shape,group_cells,groups", [
    ((4096, 8192), 1 << 25, 1), ((4096, 8192), 1 << 14, 2048),
    ((4096, 8192), 1 << 13, 4096), ((4096, 8192), 1 << 12, 4096),
    ((24, 16), 256, 3), ((24, 16), 64, 6), ((7, 16), 32, 7),
    ((0, 16), 1, 1), ((5, 0), 1, 1),
])
def test_the_extraction_s_grain_is_read_from_the_shape(
        monkeypatch, shape, group_cells, groups):
    """A power of two of rows that divides R, no more cells than
    ``SPARSIFY_GROUP_CELLS`` (itself a power of two) where a row allows
    it."""
    from combblas_tpu.ops import spgemm as ops

    shipped = ops.SPARSIFY_GROUP_CELLS
    assert shipped & (shipped - 1) == 0
    monkeypatch.setattr(ops, "SPARSIFY_GROUP_CELLS", group_cells)
    assert ops.sparsify_groups(*shape) == groups
