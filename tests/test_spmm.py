"""Round-12 batched SpMM lane: kernel golden agreement across
semirings / grids / backends with duplicate-entry COO, the SUMMA
carousel schedules, fused k-hop propagation, the serve ``"propagate"``
kind (pad-lane leak + zero-retrace), and the round-12 obs series gate.  docs/spmm.md."""

import numpy as np
import pytest

import jax

from combblas_tpu import obs
from combblas_tpu.parallel.dense import DenseParMat
from combblas_tpu.parallel.ellmat import EllParMat
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.parallel.spmm import (
    SPMM_BACKENDS,
    admissible_spmm_backends,
    dist_spmm_ell,
    pad_feature_width,
    pad_features,
    spmm_khop,
    summa_spmm,
)
from combblas_tpu.parallel.vec import DistMultiVec
from combblas_tpu.semiring import MAX_MIN, MIN_PLUS, PLUS_TIMES

SRS = {"plus_times": PLUS_TIMES, "min_plus": MIN_PLUS,
       "max_min": MAX_MIN}


@pytest.fixture
def rng():
    return np.random.default_rng(12)


def _coo(rng, n, m, dup=30):
    r = rng.integers(0, n, m)
    c = rng.integers(0, n, m)
    # duplicate entries on purpose: every backend must combine them
    # exactly (the mxu densify uses the combining scatter)
    r = np.concatenate([r, r[:dup]])
    c = np.concatenate([c, c[:dup]])
    v = rng.integers(1, 5, len(r)).astype(np.float32)
    return r, c, v


def _golden(name, r, c, v, X, n):
    F = X.shape[1]
    if name == "plus_times":
        A = np.zeros((n, n), np.float32)
        np.add.at(A, (r, c), v)
        return A @ X
    big = np.full(
        (n, F), np.inf if name == "min_plus" else -np.inf, np.float32
    )
    for rr, cc, vv in zip(r, c, v):
        if name == "min_plus":
            big[rr] = np.minimum(big[rr], vv + X[cc])
        else:
            big[rr] = np.maximum(big[rr], np.minimum(vv, X[cc]))
    return big


# -- kernel golden agreement -------------------------------------------------


@pytest.mark.parametrize("grid_shape,sr_name", [
    ((1, 1), "plus_times"), ((1, 1), "min_plus"), ((1, 1), "max_min"),
    ((2, 2), "plus_times"), ((2, 2), "min_plus"),
    # max_min on 2x2 rides the slow lane: the fold path is the same
    # scatter kernel min_plus already exercises distributed, and the
    # 1x1 case keeps the semiring covered
    pytest.param((2, 2), "max_min", marks=pytest.mark.slow),
])
def test_ell_spmm_golden(rng, grid_shape, sr_name):
    """dist_spmm_ell == dense semiring golden, dup-entry COO, every
    admissible backend, 1x1 and 2x2 grids (integer-valued f32 keeps
    plus_times f32 accumulation exact across fold orders)."""
    n, F = 72, 8
    r, c, v = _coo(rng, n, 420)
    X = rng.integers(0, 4, (n, F)).astype(np.float32)
    grid = Grid.make(*grid_shape)
    E = EllParMat.from_host_coo(grid, r, c, v, n, n)
    Xd = DistMultiVec.from_global(grid, X, align="col")
    g = _golden(sr_name, r, c, v, X, n)
    sr = SRS[sr_name]
    for backend in admissible_spmm_backends(sr):
        got = dist_spmm_ell(sr, E, Xd, backend=backend).to_global()
        np.testing.assert_array_equal(got, g, err_msg=backend)


@pytest.mark.parametrize("ring,pipeline", [
    (False, True), (True, True),
    # the unpipelined carousel is the measurement CONTROL; its golden
    # agreement is tier-1-redundant with the pipelined ring (same
    # contract path, extra compile) — slow lane
    pytest.param(True, False, marks=pytest.mark.slow),
])
def test_summa_spmm_schedules(rng, ring, pipeline):
    """SUMMA SpMM over a DenseParMat panel: gathered vs carousel vs
    unpipelined-carousel schedules all agree with the golden on the
    2x2 mesh, both backends."""
    n, F = 64, 8
    r, c, v = _coo(rng, n, 380)
    X = rng.integers(0, 3, (n, F)).astype(np.float32)
    grid = Grid.make(2, 2)
    A = SpParMat.from_global_coo(grid, r, c, v, n, n)
    Xp = DenseParMat.from_global(grid, X)
    for sr_name, backend in (
        ("plus_times", "mxu_gather"), ("min_plus", "scatter"),
    ):
        got = summa_spmm(
            SRS[sr_name], A, Xp, backend=backend, ring=ring,
            pipeline=pipeline,
        ).to_global()
        np.testing.assert_array_equal(
            got, _golden(sr_name, r, c, v, X, n),
            err_msg=f"{sr_name}/{backend}/ring={ring}",
        )


def test_summa_spmm_mxu_rejects_non_plus_times(rng):
    grid = Grid.make(2, 2)
    n = 16
    r, c, v = _coo(rng, n, 40, dup=0)
    A = SpParMat.from_global_coo(grid, r, c, v, n, n)
    Xp = DenseParMat.from_global(grid, np.ones((n, 4), np.float32))
    with pytest.raises(ValueError, match="plus_times"):
        summa_spmm(MIN_PLUS, A, Xp, backend="mxu_gather")


def test_spmm_khop_fused_and_normalized(rng):
    """spmm_khop chains hops device-resident; normalize=True equals
    the dense (D^-1 A)^k X; host features pad to pow2 lanes that stay
    zero."""
    n, F, k = 60, 6, 3
    r, c, v = _coo(rng, n, 300, dup=0)
    grid = Grid.make(2, 2)
    E = EllParMat.from_host_coo(grid, r, c, v, n, n)
    X = rng.integers(0, 3, (n, F)).astype(np.float32)
    A = np.zeros((n, n), np.float32)
    np.add.at(A, (r, c), v)

    Y = spmm_khop(PLUS_TIMES, E, X, k).to_global()
    G = X
    for _ in range(k):
        G = A @ G
    np.testing.assert_array_equal(Y[:, :F], G)
    assert Y.shape[1] == pad_feature_width(F)
    assert np.all(Y[:, F:] == 0), "pad feature lanes leaked"

    Yn = spmm_khop(PLUS_TIMES, E, X, k, normalize=True).to_global()
    # normalization is by STRUCTURAL row degree (entry count — the
    # P_ell convention), not the value-weighted row sum
    deg = np.bincount(r, minlength=n).astype(np.float32)
    M = A / np.maximum(deg, 1)[:, None]
    Gn = X
    for _ in range(k):
        Gn = M @ Gn
    np.testing.assert_allclose(Yn[:, :F], Gn, atol=1e-5)

    with pytest.raises(ValueError, match="plus_times"):
        spmm_khop(MIN_PLUS, E, X, 2, normalize=True)


def test_pad_feature_width():
    assert [pad_feature_width(f) for f in (1, 2, 3, 64, 65)] == \
        [1, 2, 4, 64, 128]
    out = pad_features(np.ones((3, 5), np.float32))
    assert out.shape == (3, 8) and np.all(out[:, 5:] == 0)


# -- serve "propagate" kind --------------------------------------------------


def _sym_graph(rng, n, m):
    r = rng.integers(0, n, m)
    c = rng.integers(0, n, m)
    return np.concatenate([r, c]), np.concatenate([c, r])


def test_serve_propagate_golden_padlanes_zero_retrace(rng):
    """The propagate kind end to end: golden per-root features on the
    2x2 mesh, PAD_ROOT lanes structurally inert (zero features, no
    leak into real lanes), zero retraces after warmup, and a
    same-shape hot-swap keeping the plan cache warm."""
    from combblas_tpu.serve import GraphEngine

    n, F = 96, 10
    rows, cols = _sym_graph(rng, n, 380)
    X = rng.integers(0, 3, (n, F)).astype(np.float32)
    grid = Grid.make(2, 2)
    eng = GraphEngine.from_coo(
        grid, rows, cols, n, features=X,
        propagate_hops=2, propagate_normalize=True,
        kinds=("bfs", "propagate"),
    )
    assert "propagate" in eng.kinds()
    eng.warmup(kinds=("propagate",), widths=(4,))
    mark = eng.trace_mark()
    out = eng.execute(
        "propagate", np.array([3, 9, -1, 57], np.int32)
    )
    feats = out["features"]
    assert feats.shape == (F, 4)  # true F, pad width stripped
    A = np.zeros((n, n), np.float32)
    A[rows, cols] = 1.0  # engine dedups: weight 1 per edge
    M = A / np.maximum(A.sum(axis=1), 1)[:, None]
    G = M @ (M @ X)
    for lane, root in ((0, 3), (1, 9), (3, 57)):
        np.testing.assert_allclose(feats[:, lane], G[root], atol=1e-5)
    assert np.all(feats[:, 2] == 0), "pad lane leaked features"
    assert eng.retraces_since(mark) == 0

    # same-shape hot-swap (features carried): still zero retraces
    v2 = eng.build_version(rows, cols)
    assert v2.X is eng.version.X  # table reused, no re-upload
    eng.swap(v2)
    eng.execute("propagate", np.array([3, 9, -1, 57], np.int32))
    assert eng.retraces_since(mark) == 0


def test_serve_propagate_through_server(rng):
    """submit() -> batcher -> scatter: each request gets ITS lane's
    feature row; an engine without features rejects the kind."""
    from combblas_tpu.serve import GraphEngine
    from combblas_tpu.serve.scheduler import ServeConfig

    n, F = 64, 6
    rows, cols = _sym_graph(rng, n, 260)
    X = rng.integers(0, 3, (n, F)).astype(np.float32)
    grid = Grid.make(2, 2)
    eng = GraphEngine.from_coo(
        grid, rows, cols, n, features=X, propagate_hops=1,
        kinds=("propagate",),
    )
    A = np.zeros((n, n), np.float32)
    A[rows, cols] = 1.0
    G = A @ X
    with eng.serve(ServeConfig(lane_widths=(1, 4),
                               max_wait_s=0.001)) as srv:
        srv.warmup()
        mark = eng.trace_mark()
        roots = [1, 5, 17, 33, 50]
        futs = [srv.submit("propagate", r) for r in roots]
        for root, f in zip(roots, futs):
            feats = f.result(timeout=60)["features"]
            assert feats.shape == (F,)
            np.testing.assert_allclose(feats, G[root], atol=1e-5)
        assert eng.retraces_since(mark) == 0

    eng2 = GraphEngine.from_coo(grid, rows, cols, n)
    assert "propagate" not in eng2.kinds()
    # the front door rejects the kind outright — never a stand-in
    with pytest.raises(ValueError, match="not built for kind"):
        eng2.plan("propagate", 1)


# -- obs round-12 series gate ------------------------------------------------


def test_round12_spmm_counters_gated(rng):
    """trace.spmm_ell / trace.spmm_khop / trace.summa_spmm land under
    obs and cost NOTHING when disabled (the zero-cost gate extended to
    the round-12 series).  Fresh static configs per phase: the trace.*
    convention counts TRACES, so an already-compiled config would
    legitimately count nothing."""
    obs.disable()
    obs.reset()
    n = 40
    r, c, v = _coo(rng, n, 160, dup=0)
    grid = Grid.make(1, 1)
    E = EllParMat.from_host_coo(grid, r, c, v, n, n)

    def panel(f):
        return DistMultiVec.from_global(
            grid, np.ones((n, f), np.float32), align="col"
        )

    assert not obs.ENABLED
    dist_spmm_ell(PLUS_TIMES, E, panel(4), backend="scatter")
    assert obs.registry.empty()  # disabled: zero bookkeeping
    obs.enable(install_hooks=False)
    try:
        dist_spmm_ell(PLUS_TIMES, E, panel(8), backend="scatter")
        assert obs.registry.get_counter(
            "trace.spmm_ell", backend="scatter", sr="plus_times"
        ) >= 1
        spmm_khop(PLUS_TIMES, E, np.ones((n, 2), np.float32), 2,
                  backend="scatter")
        assert obs.registry.get_counter(
            "trace.spmm_khop", hops=2, backend="scatter",
            normalize=False,
        ) >= 1
        A = SpParMat.from_global_coo(grid, r, c, v, n, n)
        Xp = DenseParMat.from_global(grid, np.ones((n, 4), np.float32))
        summa_spmm(PLUS_TIMES, A, Xp, backend="mxu_gather")
        assert obs.registry.get_counter(
            "trace.summa_spmm", ring=False, backend="mxu_gather"
        ) >= 1
    finally:
        obs.disable()
        obs.reset()


def test_propagate_rejects_rectangular(rng):
    """k-hop propagation needs a square operator: default kinds skip
    'propagate' on a rectangular graph; asking for it explicitly
    raises at build instead of dying mid-trace at the second hop."""
    from combblas_tpu.serve import GraphEngine

    n, m = 32, 48
    rows = rng.integers(0, n, 120)
    cols = rng.integers(0, m, 120)
    X = rng.random((m, 4)).astype(np.float32)
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), rows, cols, n, ncols=m, features=X,
        symmetric=False,
    )
    assert "propagate" not in eng.kinds()
    # the unused feature table was neither validated nor uploaded
    assert eng.version.X is None
    with pytest.raises(ValueError, match="square"):
        GraphEngine.from_coo(
            Grid.make(1, 1), rows, cols, n, ncols=m, features=X,
            symmetric=False, kinds=("propagate",),
        )
