"""Streaming mutation lane (round 11): DeltaBuffer semantics, the
incremental-merge == full-rebuild bit-exactness contract, spill paths,
and warm-restart recompute correctness.  docs/dynamic.md."""

import numpy as np
import pytest

import jax

from combblas_tpu.dynamic import (
    DeltaBatch,
    DeltaBuffer,
    DeltaOverflowError,
    apply_delta,
    fold_ops,
)
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import GraphEngine


def _sym_coo(rng, n, m):
    r = rng.integers(0, n, m)
    c = rng.integers(0, n, m)
    return np.concatenate([r, c]), np.concatenate([c, r])


def _weighted_engine(rng, grid, n=96, m=500, kinds=None):
    rows, cols = _sym_coo(rng, n, m)
    w = rng.random(len(rows)).astype(np.float32) + 0.1
    return (
        GraphEngine.from_coo(
            grid, rows, cols, n, weights=w, keep_coo=True, kinds=kinds
        ),
        rows, cols, w,
    )


def _assert_versions_bitexact(v_inc, v_gold):
    """The acceptance contract: every artifact of the incremental
    version equals the full from_coo rebuild BIT-EXACTLY (canonical COO
    comparison — layout-independent)."""
    for name in ("E", "E_weighted", "P_ell", "ET"):
        a, b = getattr(v_inc, name), getattr(v_gold, name)
        assert (a is None) == (b is None), name
        if a is None:
            continue
        ra, ca, va = a.to_host_coo()
        rb, cb, vb = b.to_host_coo()
        assert np.array_equal(ra, rb), f"{name} rows differ"
        assert np.array_equal(ca, cb), f"{name} cols differ"
        assert np.array_equal(va, vb), f"{name} vals differ"
    assert np.array_equal(v_inc.deg, v_gold.deg)
    assert np.array_equal(v_inc.outdeg, v_gold.outdeg)
    assert (v_inc.dangling is None) == (v_gold.dangling is None)
    if v_inc.dangling is not None:
        assert np.array_equal(
            np.asarray(jax.device_get(v_inc.dangling.blocks)),
            np.asarray(jax.device_get(v_gold.dangling.blocks)),
        )
    assert v_inc.nnz == v_gold.nnz


def _golden_rebuild(engine, version):
    """Full from_coo-pipeline rebuild of the merged edge list."""
    r, c, _n = version.host_coo
    return engine.build_version(
        r, c, weights=version.host_weights, keep_coo=True
    )


# -- DeltaBuffer -------------------------------------------------------------


def test_delta_buffer_bounded_and_tickets():
    buf = DeltaBuffer(capacity=4, nrows=10, ncols=10)
    s0 = buf.add("insert", 1, 2, 0.5)
    s1 = buf.add_many([("delete", 2, 3), ("upsert", 3, 4, 2.0)])
    assert (s0, s1) == (0, 2)
    assert buf.depth() == 3
    with pytest.raises(DeltaOverflowError):
        buf.add_many([("insert", 0, 0), ("insert", 0, 1)])  # 3+2 > 4
    assert buf.depth() == 3  # atomic: nothing was admitted
    batch = buf.drain()
    assert len(batch) == 3 and batch.last_seq == 2
    assert buf.drain() is None
    # sequence numbers keep rising across drains
    assert buf.add("insert", 5, 5) == 3


def test_delta_buffer_validates():
    buf = DeltaBuffer(capacity=8, nrows=4, ncols=4)
    with pytest.raises(ValueError):
        buf.add("insert", 4, 0)  # row out of range
    with pytest.raises(ValueError):
        buf.add("frobnicate", 0, 0)  # unknown op
    with pytest.raises(ValueError):
        buf.add_many([("insert", 0, 0), ("insert", 0, 9)])  # atomic
    assert buf.depth() == 0
    with pytest.raises(ValueError):
        DeltaBuffer(combine="median")


def _replay_naive(ops, base, combine):
    """Sequential per-op replay — the semantics fold_ops must match."""
    state = dict(base)  # key -> weight
    for op, k, w in ops:
        if op == "insert":
            state[k] = w
        elif op == "delete":
            state.pop(k, None)
        else:  # upsert
            if k not in state:
                state[k] = w
            elif combine == "min":
                state[k] = min(state[k], w)
            elif combine == "max":
                state[k] = max(state[k], w)
            elif combine == "sum":
                state[k] = state[k] + w
            else:  # last
                state[k] = w
    return state


@pytest.mark.parametrize("combine", ["min", "max", "sum", "last"])
def test_fold_ops_matches_sequential_replay(rng, combine):
    ncols = 16
    base_keys = np.sort(
        rng.choice(ncols * ncols, size=40, replace=False)
    ).astype(np.int64)
    # weights are multiples of 1/64 so float32 sums are EXACT in any
    # association order (the fold reduces upserts before combining with
    # the base; sequential replay combines left-to-right)
    base_w = (rng.integers(1, 512, 40) / 64.0).astype(np.float32)
    # random op stream with heavy duplicate-key pressure
    m = 120
    keys = rng.choice(base_keys.tolist() + [7, 33, 99, 254], size=m)
    opnames = rng.choice(["insert", "delete", "upsert"], size=m)
    vals = (rng.integers(1, 512, m) / 64.0).astype(np.float32)
    batch = DeltaBatch.from_ops([
        (opnames[i], int(keys[i] // ncols), int(keys[i] % ncols),
         float(vals[i]))
        for i in range(m)
    ])
    uniq, present, fw = fold_ops(
        batch, base_keys, base_w, ncols, combine
    )
    ref = _replay_naive(
        [(opnames[i], int(keys[i]), float(vals[i])) for i in range(m)],
        dict(zip(base_keys.tolist(), base_w.tolist())),
        combine,
    )
    for k, p, w in zip(uniq.tolist(), present.tolist(), fw.tolist()):
        assert p == (k in ref), (k, combine)
        if p:
            assert np.float32(w) == np.float32(ref[k]), (k, combine)


# -- incremental merge == full rebuild ---------------------------------------


@pytest.mark.parametrize("gridshape", [
    # 1x1 is slow-lane (round 12, tier-1 budget): the 2x2 case keeps
    # the bit-exactness contract on the grid with per-tile slack, and
    # the 1x1 spill paths have their own dedicated tests
    pytest.param((1, 1), marks=pytest.mark.slow),
    (2, 2),
])
def test_apply_delta_bitexact(rng, gridshape):
    """The acceptance gate: insert/delete/upsert batches — with
    duplicate keys inside one batch — merge bit-exactly equal to the
    full from_coo rebuild, on 1x1 AND 2x2 grids, and the incremental
    path preserves every operand shape (zero retraces after swap)."""
    grid = Grid.make(*gridshape)
    eng, rows, cols, _w = _weighted_engine(rng, grid)
    n = eng.nrows
    key = rows.astype(np.int64) * n + cols
    er, ec = np.divmod(np.unique(key), n)
    ops = []
    for t in range(4):  # symmetric deletes of existing edges
        ops.append(("delete", int(er[t * 11]), int(ec[t * 11])))
        ops.append(("delete", int(ec[t * 11]), int(er[t * 11])))
    # duplicate-key sequences: insert then delete then re-insert, and
    # stacked upserts (the fold must replay them in admission order)
    ops += [
        ("insert", 1, 2, 9.0), ("delete", 1, 2), ("insert", 1, 2, 3.5),
        ("insert", 2, 1, 3.5),
        ("upsert", int(er[50]), int(ec[50]), 0.05),
        ("upsert", int(er[50]), int(ec[50]), 0.01),
        ("upsert", int(ec[50]), int(er[50]), 0.01),
        ("insert", 7, 9, 1.25), ("insert", 9, 7, 1.25),
    ]
    eng.warmup(widths=(1, 2))
    mark = eng.trace_mark()
    v1 = apply_delta(
        eng.version, DeltaBatch.from_ops(ops), kinds=eng.kinds()
    )
    st = v1.dyn.last_stats
    assert st.mode == "incremental", (st.mode, st.reason)
    assert st.rows_patched > 0
    assert st.buckets_reused > 0  # untouched classes share device arrays
    _assert_versions_bitexact(v1, _golden_rebuild(eng, v1))
    eng.swap(v1)
    eng.execute("bfs", np.asarray([1], np.int32))
    eng.execute("sssp", np.asarray([1, 2], np.int32))
    assert eng.retraces_since(mark) == 0


def test_apply_delta_directed_bc_transpose(rng):
    """The transpose twin (ET, bc on directed graphs) is patched
    through the second orientation and stays bit-exact."""
    grid = Grid.make(2, 2)
    n, m = 64, 300
    rows = rng.integers(0, n, m)
    cols = rng.integers(0, n, m)
    eng = GraphEngine.from_coo(
        grid, rows, cols, n, kinds=("bfs", "bc"), symmetric=False,
        keep_coo=True,
    )
    assert eng.version.ET is not None
    ops = [
        ("insert", 0, 5), ("insert", 5, 0), ("delete", int(rows[0]),
                                             int(cols[0])),
        ("insert", 10, 11),
    ]
    v1 = apply_delta(
        eng.version, DeltaBatch.from_ops(ops), kinds=eng.kinds()
    )
    assert v1.dyn.last_stats.mode == "incremental"
    r1, c1, _ = v1.host_coo
    v_gold = eng.build_version(r1, c1, symmetric=False, keep_coo=True)
    _assert_versions_bitexact(v1, v_gold)


def test_apply_delta_spill_threshold(rng):
    """A delta past the structural-change fraction spills to a full
    rebuild — and the rebuild is bit-exact too (the spill path IS the
    from_coo pipeline plus retained state)."""
    grid = Grid.make(1, 1)
    eng, _rows, _cols, _w = _weighted_engine(rng, grid, n=64, m=250)
    n = eng.nrows
    ops = []
    for i in range(n):  # dense new clique rows: far past 10%
        for j in (1, 3, 5):
            ops.append(("insert", i, (i + j) % n, 1.0))
            ops.append(("insert", (i + j) % n, i, 1.0))
    v1 = apply_delta(
        eng.version, DeltaBatch.from_ops(ops), kinds=eng.kinds()
    )
    st = v1.dyn.last_stats
    assert st.mode == "rebuild" and st.reason == "threshold"
    _assert_versions_bitexact(v1, _golden_rebuild(eng, v1))


def test_apply_delta_bucket_full_spill():
    """No free slot anywhere -> honest rebuild (growing a bucket would
    change operand shapes and retrace regardless)."""
    grid = Grid.make(1, 1)
    n = 8
    rows = np.arange(n)
    cols = (rows + 1) % n  # every row degree 1: the class is FULL
    rows_s = np.concatenate([rows, cols])
    cols_s = np.concatenate([cols, rows])
    eng = GraphEngine.from_coo(
        grid, rows_s, cols_s, n, kinds=("bfs",), keep_coo=True
    )
    v1 = apply_delta(
        eng.version,
        DeltaBatch.from_ops([("insert", 0, 4), ("insert", 4, 0)]),
        kinds=eng.kinds(), spill_frac=1.0,  # isolate the capacity spill
    )
    st = v1.dyn.last_stats
    assert st.mode == "rebuild" and st.reason == "bucket_full"
    _assert_versions_bitexact(v1, _golden_rebuild(eng, v1))


def test_apply_delta_chain(rng):
    """Merge state evolves correctly across a chain of deltas: the end
    state equals one rebuild of the final edge list."""
    grid = Grid.make(2, 2)
    eng, rows, cols, _w = _weighted_engine(rng, grid, n=64, m=300)
    n = eng.nrows
    v = eng.version
    for step in range(4):
        a, b = int(rng.integers(0, n)), int(rng.integers(0, n))
        ops = [
            ("insert", a, b, 0.5 + step), ("insert", b, a, 0.5 + step),
            ("upsert", int(rows[step]), int(cols[step]), 0.01),
            ("upsert", int(cols[step]), int(rows[step]), 0.01),
        ]
        v = apply_delta(v, DeltaBatch.from_ops(ops), kinds=eng.kinds())
        eng.swap(v)
    _assert_versions_bitexact(v, _golden_rebuild(eng, v))


def test_apply_delta_requires_host_coo(rng):
    grid = Grid.make(1, 1)
    rows, cols = _sym_coo(rng, 32, 100)
    eng = GraphEngine.from_coo(grid, rows, cols, 32)  # no keep_coo
    with pytest.raises(ValueError, match="keep_coo"):
        apply_delta(
            eng.version, DeltaBatch.from_ops([("insert", 0, 1)]),
            kinds=eng.kinds(),
        )


def test_symmetry_guard_for_bc(rng):
    """A bc-serving symmetric engine (E is its own transpose) must
    reject a delta that breaks structural symmetry — the same check
    from_coo performs at build."""
    grid = Grid.make(1, 1)
    rows, cols = _sym_coo(rng, 32, 120)
    eng = GraphEngine.from_coo(
        grid, rows, cols, 32, kinds=("bfs", "bc"), keep_coo=True
    )
    r0, c0, _ = eng.version.host_coo
    present = set(zip(r0.tolist(), c0.tolist()))
    a, b = next(
        (a, b) for a in range(32) for b in range(32)
        if a != b and (a, b) not in present
    )
    with pytest.raises(ValueError, match="symmetr"):
        apply_delta(
            eng.version,
            DeltaBatch.from_ops([("insert", a, b)]),  # no (b, a) twin
            kinds=eng.kinds(),
        )


# -- warm-restart recompute --------------------------------------------------


def _mutable_engine(rng, n=96, m=500):
    grid = Grid.make(2, 2)
    rows, cols = _sym_coo(rng, n, m)
    return GraphEngine.from_coo(
        grid, rows, cols, n, kinds=("bfs", "pagerank"), keep_coo=True
    ), rows


def test_refresh_cold_then_cached(rng):
    eng, rows = _mutable_engine(rng)
    root = int(rows[0])
    first = eng.refresh("bfs", root=root)
    assert first["mode"] == "cold" and first["result"].shape == (96,)
    again = eng.refresh("bfs", root=root)
    assert again["mode"] == "cached"
    assert np.array_equal(first["result"], again["result"])


def test_refresh_warm_matches_cold_after_inserts(rng):
    """Insert-only deltas: BFS/CC repair from the previous result is
    EXACT (monotone relaxation), and PageRank restarts from the
    previous vector in fewer iterations."""
    eng, rows = _mutable_engine(rng)
    root = int(rows[0])
    eng.refresh("bfs", root=root)
    eng.refresh("cc")
    pr_cold = eng.refresh("pagerank")
    far = int(np.argmax(eng.refresh("bfs", root=root)["result"]))
    ops = [("insert", root, far), ("insert", far, root),
           ("insert", 2, 3), ("insert", 3, 2)]
    eng.swap(eng.apply_delta(DeltaBatch.from_ops(ops)))
    warm_bfs = eng.refresh("bfs", root=root)
    assert warm_bfs["mode"] == "warm"
    cold_bfs = eng.refresh("bfs", root=root, force_cold=True)
    assert np.array_equal(warm_bfs["result"], cold_bfs["result"])
    warm_cc = eng.refresh("cc")
    assert warm_cc["mode"] == "warm"
    cold_cc = eng.refresh("cc", force_cold=True)
    assert np.array_equal(warm_cc["result"], cold_cc["result"])
    warm_pr = eng.refresh("pagerank")
    assert warm_pr["mode"] == "warm"
    assert warm_pr["niter"] <= pr_cold["niter"]
    cold_pr = eng.refresh("pagerank", force_cold=True)
    np.testing.assert_allclose(
        warm_pr["result"], cold_pr["result"], atol=5e-5
    )


def test_refresh_deletes_fall_back_cold(rng):
    """Deletions can RAISE bfs levels / split components — no monotone
    repair expresses that, so the refresh honestly recomputes."""
    eng, rows = _mutable_engine(rng)
    root = int(rows[0])
    eng.refresh("bfs", root=root)
    r, c, _ = eng.version.host_coo
    # delete one symmetric pair not incident to the root
    pick = next(
        i for i in range(len(r)) if r[i] != root and c[i] != root
        and r[i] != c[i]
    )
    ops = [("delete", int(r[pick]), int(c[pick])),
           ("delete", int(c[pick]), int(r[pick]))]
    eng.swap(eng.apply_delta(DeltaBatch.from_ops(ops)))
    out = eng.refresh("bfs", root=root)
    assert out["mode"] == "cold" and out["cold_reason"] == "deletes"
    # and the cold result is trusted fresh state: a further cached read
    assert eng.refresh("bfs", root=root)["mode"] == "cached"


def test_refresh_validates(rng):
    eng, _rows = _mutable_engine(rng, n=32, m=100)
    with pytest.raises(ValueError, match="root"):
        eng.refresh("bfs")
    with pytest.raises(ValueError, match="unknown refresh kind"):
        eng.refresh("toposort")


# -- round 12: headroom-aware bucket sizing + the no-op CSC reset fix --------


def test_headroom_avoids_bucket_full_spill():
    """The SAME degree-1 ring that spills ``bucket_full`` when built
    tight merges INCREMENTALLY when the build reserved headroom slots
    — the growing row re-buckets into the free reserve
    (``headroom_used``) and the result stays bit-exact with the full
    rebuild."""
    grid = Grid.make(1, 1)
    n = 8
    rows = np.arange(n)
    cols = (rows + 1) % n
    rows_s = np.concatenate([rows, cols])
    cols_s = np.concatenate([cols, rows])
    eng = GraphEngine.from_coo(
        grid, rows_s, cols_s, n, kinds=("bfs",), keep_coo=True,
        headroom=0.5,
    )
    assert eng.version.headroom == 0.5
    batch = DeltaBatch.from_ops([("insert", 0, 4), ("insert", 4, 0)])
    v1 = apply_delta(
        eng.version, batch, kinds=eng.kinds(), spill_frac=1.0,
    )
    st = v1.dyn.last_stats
    assert st.mode == "incremental", st.reason
    assert st.headroom_used > 0
    assert st.rows_rebucketed > 0
    _assert_versions_bitexact(v1, _golden_rebuild(eng, v1))
    # identical operand shapes: the zero-retrace contract's premise
    for b_new, b_old in zip(v1.E.buckets, eng.version.E.buckets):
        assert b_new[0].shape == b_old[0].shape


def test_headroom_env_default(monkeypatch):
    """COMBBLAS_DYNAMIC_HEADROOM drives builds that don't pass
    headroom= explicitly (and bucket shapes grow by the slack)."""
    from combblas_tpu.parallel.ellmat import EllParMat

    grid = Grid.make(1, 1)
    n = 8
    rows = np.arange(n)
    cols = (rows + 1) % n
    tight = EllParMat.host_build(
        grid, rows, cols, np.ones(n, np.float32), n, n
    )
    monkeypatch.setenv("COMBBLAS_DYNAMIC_HEADROOM", "1.0")
    slack = EllParMat.host_build(
        grid, rows, cols, np.ones(n, np.float32), n, n
    )
    assert slack[0][0].shape[2] == 2 * tight[0][0].shape[2]


def test_csc_companion_survives_noop_merge(rng):
    """REGRESSION (round 12): a fold that touched no edges (upsert of
    an already-present edge) must CARRY the CSC companion and the
    cached coldeg instead of resetting them to a rebuild-from-COO.  A
    structural change resets coldeg, and keeps the companion's arrays
    for their shapes (the BFS plan's operand) marked not-current."""
    eng, rows, cols, _w = _weighted_engine(rng, Grid.make(2, 2))
    sentinel_csc = object()
    sentinel_coldeg = object()
    eng.csc = sentinel_csc
    eng.coldeg = sentinel_coldeg
    r0, c0 = int(rows[0]), int(cols[0])
    # structurally NO-OP: the edge exists and min-combining a larger
    # weight keeps the stored one -> ins/rem/wchg all empty
    noop = DeltaBatch.from_ops([("upsert", r0, c0, 123.0)])
    v1 = apply_delta(eng.version, noop, kinds=eng.kinds())
    assert v1.dyn.last_stats.mode == "incremental"
    assert v1.dyn.last_stats.inserted == 0
    assert v1.dyn.last_stats.removed == 0
    assert v1.csc is sentinel_csc and v1.csc_current
    assert v1.coldeg is sentinel_coldeg
    # a real structural change resets coldeg (lazily rebuilt) and marks
    # the companion
    free = next(
        (a, b) for a in range(3) for b in range(3)
        if not np.any((rows == a) & (cols == b)) and a != b
    )
    real = DeltaBatch.from_ops([
        ("insert", free[0], free[1], 1.0),
        ("insert", free[1], free[0], 1.0),
    ])
    v2 = apply_delta(eng.version, real, kinds=eng.kinds())
    assert v2.csc is sentinel_csc and not v2.csc_current
    assert v2.coldeg is None


def test_symmetry_guard_covers_propagate(rng):
    """A propagate-serving symmetric engine (ET is None: E is its own
    transpose) must reject asymmetric deltas exactly like bc — a
    silent merge would flip the edge direction every served
    propagation walks."""
    n = 64
    rows, cols = _sym_coo(rng, n, 300)
    X = rng.random((n, 4)).astype(np.float32)
    eng = GraphEngine.from_coo(
        Grid.make(2, 2), rows, cols, n, keep_coo=True,
        features=X, kinds=("bfs", "propagate"),
    )
    free = next(
        (a, b) for a in range(4) for b in range(4)
        if a != b and not np.any((rows == a) & (cols == b))
    )
    with pytest.raises(ValueError, match="symmetry"):
        apply_delta(
            eng.version,
            DeltaBatch.from_ops([("insert", free[0], free[1])]),
            kinds=eng.kinds(),
        )
