"""Semantic graphs / filtered BFS+MIS, forced spans, checkpointing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu.models.bfs import bfs, validate_bfs_tree
from combblas_tpu.models.mis import mis
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.parallel.vec import DistVec
from combblas_tpu.semantic import SemanticGraph, filtered_bfs, filtered_mis
from combblas_tpu import obs
from combblas_tpu.utils import checkpoint as ckpt
from conftest import random_dense


def _twitterish_graph(rng, n, density=0.25):
    """Symmetric structure with per-edge (latest, follower) attributes."""
    d = (rng.random((n, n)) < density).astype(np.float32)
    d = np.maximum(d, d.T)
    np.fill_diagonal(d, 0)
    r, c = np.nonzero(d)
    # symmetric attribute so the filtered graph stays symmetric
    latest = ((r * 131 + c * 17) % 100 + ((c * 131 + r * 17) % 100)).astype(
        np.float32
    )
    followers = ((r + c) % 7).astype(np.int32)
    return d, r, c, {"latest": latest, "followers": followers}


def _keep_early(attrs):
    return attrs["latest"] < 100


def test_materialize_vs_mask_structure(rng):
    grid = Grid.make(2, 2)
    d, r, c, attrs = _twitterish_graph(rng, 16)
    g = SemanticGraph.from_edges(grid, r, c, attrs, 16, 16)
    mat = g.materialize(_keep_early).to_dense()
    msk = g.mask(_keep_early).to_dense()
    keep = attrs["latest"] < 100
    expect = np.zeros((16, 16), np.float32)
    expect[r[keep], c[keep]] = 1.0
    np.testing.assert_allclose(mat, expect)
    np.testing.assert_allclose(msk, expect)  # mask writes 0/1 values


def test_filtered_bfs_modes_agree(rng):
    grid = Grid.make(2, 2)
    d, r, c, attrs = _twitterish_graph(rng, 20)
    g = SemanticGraph.from_edges(grid, r, c, attrs, 20, 20)
    p1, l1, _ = filtered_bfs(g, _keep_early, 0, materialize=True)
    p2, l2, _ = filtered_bfs(g, _keep_early, 0, materialize=False)
    np.testing.assert_array_equal(l1.to_global(), l2.to_global())
    filt = g.materialize(_keep_early).to_dense()
    assert not validate_bfs_tree(filt, 0, p1.to_global(), l1.to_global())
    assert not validate_bfs_tree(filt, 0, p2.to_global(), l2.to_global())


def test_filtered_bfs_differs_from_unfiltered(rng):
    grid = Grid.make(2, 2)
    d, r, c, attrs = _twitterish_graph(rng, 20, density=0.4)
    g = SemanticGraph.from_edges(grid, r, c, attrs, 20, 20)
    _, l_all, _ = bfs(g.structure, 0)
    _, l_f, _ = filtered_bfs(g, lambda a: a["latest"] < 40, 0)
    assert not np.array_equal(l_all.to_global(), l_f.to_global())


def test_filtered_mis_independent(rng):
    grid = Grid.make(2, 2)
    d, r, c, attrs = _twitterish_graph(rng, 16, density=0.3)
    g = SemanticGraph.from_edges(grid, r, c, attrs, 16, 16)
    inset, _ = filtered_mis(g, _keep_early, jax.random.key(0))
    filt = g.materialize(_keep_early).to_dense()
    s = (np.asarray(inset.to_global()) == 1)[:16]  # status: 1=in, -1=out
    # independence in the filtered graph
    sub = filt[np.ix_(s.nonzero()[0], s.nonzero()[0])]
    assert sub.sum() == 0


def test_forced_spans_accumulate():
    obs.reset_spans()
    with obs.span("unit_test_phase", force=True):
        x = jnp.arange(8).sum()
    rep = obs.report()
    assert "unit_test_phase" in rep
    sec, n = rep["unit_test_phase"]
    assert n == 1 and sec >= 0


def test_checkpoint_npz_roundtrip(tmp_path, rng):
    grid = Grid.make(2, 2)
    d = random_dense(rng, 12, 12, 0.3)
    A = SpParMat.from_dense(grid, d)
    p = str(tmp_path / "mat.npz")
    ckpt.save(p, A)
    B = ckpt.load(p, grid)
    np.testing.assert_allclose(B.to_dense(), d)
    # cross-shape restore (re-shard via global tuples)
    g2 = Grid.make(2, 4)
    C = ckpt.load(p, g2)
    np.testing.assert_allclose(C.to_dense(), d)
    v = DistVec.from_global(grid, np.arange(10, dtype=np.float32))
    pv = str(tmp_path / "vec.npz")
    ckpt.save(pv, v)
    np.testing.assert_allclose(
        ckpt.load(pv, grid).to_global(), np.arange(10)
    )


def test_checkpoint_orbax_roundtrip(tmp_path, rng):
    pytest.importorskip("orbax.checkpoint")
    grid = Grid.make(2, 2)
    d = random_dense(rng, 12, 12, 0.3)
    A = SpParMat.from_dense(grid, d)
    p = str(tmp_path / "omat")
    ckpt.save_orbax(p, A)
    B = ckpt.load_orbax(p, grid)
    np.testing.assert_allclose(B.to_dense(), d)


def test_checkpoint_vec_preserves_fill(tmp_path):
    """Restored vectors must keep their padding fill (ADVICE r1): a MAX
    reduce over an all-negative vector restored with 0-padding would
    silently return 0."""
    from combblas_tpu.semiring import SELECT2ND_MAX

    grid = Grid.make(2, 2)
    x = -np.arange(2, 9, dtype=np.int32)  # 7 values, all negative
    v = DistVec.from_global(grid, x, align="row", fill=np.int32(-(2**31)))
    p = str(tmp_path / "negvec.npz")
    ckpt.save(p, v)
    # same-shape restore: padded blocks verbatim
    v2 = ckpt.load(p, grid)
    assert int(v2.reduce(SELECT2ND_MAX)) == -2
    np.testing.assert_array_equal(v2.to_global(), x)
    # cross-shape restore: fill persisted through meta
    g2 = Grid.make(4, 2)
    v3 = ckpt.load(p, g2)
    assert int(v3.reduce(SELECT2ND_MAX)) == -2
    np.testing.assert_array_equal(v3.to_global(), x)
