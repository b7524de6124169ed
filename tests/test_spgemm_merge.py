"""Round-13 merge tiers: sorted-run union, hash accumulate, 3D
carousel — property tests.

Every merge tier must be BIT-EXACT with the classic concat+sort
combine (values included: duplicate groups fold in identical operand
order for ``runs``; test values are small integers so the hash tier's
unordered float adds are exact too), the hash tier's counted overflow
must fall back to a sorted tier rather than truncate, and the merge
knob must resolve arg > store > env > heuristic.  Heavy grid/semiring
variants ride ``-m slow`` with one fast tier-1 representative each
(the PR 7/10 budget precedent).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from combblas_tpu import MAX_MIN, MIN_PLUS, PLUS_TIMES, obs
from combblas_tpu.ops.spgemm import (
    hash_merge,
    hash_table_capacity,
    merge_sorted_runs,
)
from combblas_tpu.ops.tuples import SpTuples
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.mesh3d import Grid3D, SpParMat3D, spgemm3d
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.parallel.spgemm import spgemm

SEMIRINGS = {
    "plus_times": PLUS_TIMES,
    "min_plus": MIN_PLUS,
    "max_min": MAX_MIN,
}


@pytest.fixture
def rng():
    return np.random.default_rng(1313)


def _sorted_run(rng, nrows, ncols, n, cap):
    r = rng.integers(0, nrows, n)
    c = rng.integers(0, ncols, n)
    v = rng.integers(1, 5, n).astype(np.float32)
    order = np.lexsort((c, r))
    return SpTuples.from_coo(
        r[order], c[order], v[order], nrows, ncols, capacity=cap
    )


# FIXED run capacity for the unit tests: every (L, semiring) case
# shares compiled kernels (capacities are trace-time statics — random
# ones minted one XLA compile per case and dominated the tier-1 bill)
_UNIT_CAP = 48


def _coo_canon(C):
    gr, gc, gv = C.to_global_coo()
    o = np.lexsort((np.asarray(gc), np.asarray(gr)))
    return (
        np.asarray(gr)[o], np.asarray(gc)[o], np.asarray(gv)[o]
    )


def _assert_same(a, b, ctx=None):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y, err_msg=str(ctx))


# --- unit: the merge kernels -------------------------------------------------


@pytest.mark.parametrize(
    "srname",
    [
        "plus_times",
        pytest.param("min_plus", marks=pytest.mark.slow),
        pytest.param("max_min", marks=pytest.mark.slow),
    ],
)
def test_merge_sorted_runs_matches_concat_sort(rng, srname):
    """Rank-space union == stable concat+sort: same entry order
    (duplicates adjacent, ties in run order), padding a strict suffix,
    and compact(assume_sorted) agreeing."""
    nrows, ncols = 37, 29
    sr = SEMIRINGS[srname]
    for L in (1, 2, 3, 5):
        runs = [
            _sorted_run(rng, nrows, ncols, int(rng.integers(0, 40)),
                        _UNIT_CAP)
            for _ in range(L)
        ]
        merged = merge_sorted_runs(runs)
        concat = SpTuples.concat(runs).sort_rowmajor()
        assert int(merged.nnz) == int(concat.nnz)
        m = np.asarray(merged.rows) < nrows
        cm = np.asarray(concat.rows) < nrows
        np.testing.assert_array_equal(
            np.asarray(merged.rows)[m], np.asarray(concat.rows)[cm]
        )
        np.testing.assert_array_equal(
            np.asarray(merged.cols)[m], np.asarray(concat.cols)[cm]
        )
        # duplicate groups must fold in IDENTICAL operand order (the
        # bit-exactness contract): compare the uncombined value streams
        np.testing.assert_array_equal(
            np.asarray(merged.vals)[m], np.asarray(concat.vals)[cm]
        )
        # padding is a strict suffix (valid_mask semantics survive)
        if (~m).any():
            assert not m[np.argmax(~m):].any()
        a, da = merged.compact_counted(
            sr, capacity=merged.capacity, assume_sorted=True
        )
        b, db = concat.compact_counted(
            sr, capacity=concat.capacity, assume_sorted=True
        )
        assert int(da) == int(db)
        ka = np.asarray(a.valid_mask())
        kb = np.asarray(b.valid_mask())
        np.testing.assert_array_equal(
            np.asarray(a.rows)[ka], np.asarray(b.rows)[kb]
        )
        np.testing.assert_array_equal(
            np.asarray(a.vals)[ka], np.asarray(b.vals)[kb]
        )


@pytest.mark.parametrize(
    "srname",
    [
        "plus_times",
        pytest.param("min_plus", marks=pytest.mark.slow),
        pytest.param("max_min", marks=pytest.mark.slow),
    ],
)
def test_hash_merge_matches_compact(rng, srname):
    """The bounded open-addressing combine produces exactly compact()'s
    (key, value) set — any order — with zero overflow at the sized
    table, exact distinct count, and a COUNTED (not silent) overflow
    when the table is deliberately too small."""
    nrows, ncols = 41, 23
    sr = SEMIRINGS[srname]
    cap, table = 207, hash_table_capacity(200)
    for n in (0, 1, 17, 200):
        t = _sorted_run(rng, nrows, ncols, n, cap)
        ref = t.compact(sr, capacity=cap)
        out, over, distinct = hash_merge(
            sr, t, out_capacity=cap, table_capacity=table,
        )
        assert int(over) == 0, (srname, n)
        assert int(distinct) == int(ref.nnz)
        kr = np.asarray(ref.valid_mask())
        ko = np.asarray(out.valid_mask())
        ra = np.lexsort(
            (np.asarray(ref.cols)[kr], np.asarray(ref.rows)[kr])
        )
        oa = np.lexsort(
            (np.asarray(out.cols)[ko], np.asarray(out.rows)[ko])
        )
        for refa, outa in (
            (ref.rows, out.rows), (ref.cols, out.cols),
            (ref.vals, out.vals),
        ):
            np.testing.assert_array_equal(
                np.asarray(refa)[kr][ra], np.asarray(outa)[ko][oa],
                err_msg=f"{srname} n={n}",
            )
    # deliberately undersized table: overflow is COUNTED
    t = _sorted_run(rng, nrows, ncols, 200, 210)
    _, over, _ = hash_merge(
        PLUS_TIMES, t, out_capacity=256, table_capacity=16, n_probes=4
    )
    assert int(over) > 0


# --- 2D ESC stage-chunk merge ------------------------------------------------


def _rand_square(rng, grid, n=64, m=500):
    r = rng.integers(0, n, m)
    c = rng.integers(0, n, m)
    v = rng.integers(1, 4, m).astype(np.float32)  # duplicate COO keys
    return SpParMat.from_global_coo(grid, r, c, v, n, n)


@pytest.mark.parametrize(
    "gshape,srname",
    [
        pytest.param((2, 2), "plus_times"),
        pytest.param((2, 2), "min_plus", marks=pytest.mark.slow),
        pytest.param((2, 2), "max_min", marks=pytest.mark.slow),
        pytest.param((1, 1), "plus_times", marks=pytest.mark.slow),
        pytest.param((1, 1), "min_plus", marks=pytest.mark.slow),
        pytest.param((1, 1), "max_min", marks=pytest.mark.slow),
    ],
)
def test_esc2d_merge_runs_bitexact(rng, gshape, srname):
    """summa_spgemm(merge='runs') — per-stage sorts + rank-space union
    — is bit-exact with the classic concat+sort on duplicate COO."""
    grid = Grid.make(*gshape)
    A = _rand_square(rng, grid)
    sr = SEMIRINGS[srname]
    _assert_same(
        _coo_canon(spgemm(sr, A, A, merge="sort")),
        _coo_canon(spgemm(sr, A, A, merge="runs")),
        (gshape, srname),
    )


# --- 3D fiber-reduce merge tiers + carousel ---------------------------------


def _mats3d(rng, n=64, m=500, layers=2):
    g3 = Grid3D.make(layers, 2, 2)
    r = rng.integers(0, n, m)
    c = rng.integers(0, n, m)
    v = rng.integers(1, 4, m).astype(np.float32)
    A3 = SpParMat3D.from_global_coo(g3, r, c, v, n, n, split="col")
    B3 = SpParMat3D.from_global_coo(g3, r, c, v, n, n, split="row")
    return A3, B3


@pytest.mark.parametrize(
    "tier,merge,kw,srname",
    [
        # fast representatives: one per (tier, merge) pair; the
        # SERIAL windowed+runs case joined the slow set in round 17
        # (tier-1 budget) — the ring=True case below keeps the
        # windowed+runs fiber merge bit-exactness in tier-1, and
        # esc+runs covers the serial schedule
        pytest.param("windowed", "runs", {}, "plus_times",
                     marks=pytest.mark.slow),
        pytest.param("windowed", "hash", {}, "plus_times"),
        pytest.param("esc", "runs", {}, "plus_times"),
        pytest.param("esc", "hash", {}, "min_plus",
                     marks=pytest.mark.slow),
        pytest.param("windowed", "runs", {}, "min_plus",
                     marks=pytest.mark.slow),
        pytest.param("windowed", "runs", {}, "max_min",
                     marks=pytest.mark.slow),
        pytest.param("esc", "runs", {}, "max_min",
                     marks=pytest.mark.slow),
        # carousel vs gathered (the round-13 3D ring): fast windowed
        # pipelined representative; serial control + ESC ring slow
        pytest.param("windowed", "runs", {"ring": True}, "plus_times"),
        pytest.param(
            "windowed", "runs", {"ring": True, "pipeline": False},
            "plus_times", marks=pytest.mark.slow,
        ),
        pytest.param("esc", "sort", {"ring": True}, "plus_times",
                     marks=pytest.mark.slow),
    ],
)
def test_spgemm3d_merge_tiers_bitexact(rng, tier, merge, kw, srname):
    """Every merge tier (and the per-layer carousel schedule) agrees
    bit-exactly with the gathered concat+sort path on the L2x2x2 mesh
    with duplicate COO."""
    sr = SEMIRINGS[srname]
    A3, B3 = _mats3d(rng)
    golden = _coo_canon(spgemm3d(sr, A3, B3, tier=tier, merge="sort"))
    got = _coo_canon(spgemm3d(sr, A3, B3, tier=tier, merge=merge, **kw))
    _assert_same(golden, got, (tier, merge, kw, srname))


def test_hash_overflow_falls_back_to_runs(rng, monkeypatch):
    """A hash table that cannot place its entries must COUNT the
    overflow and transparently rerun through the sorted-runs tier —
    never truncate.  n_probes=0 guarantees nothing places; a DISTINCT
    matrix size keeps the crippled trace out of the jit cache other
    tests share."""
    from combblas_tpu.parallel import mesh3d

    monkeypatch.setattr(mesh3d, "HASH_MERGE_PROBES", 0)
    A3, B3 = _mats3d(rng, n=32, m=300)
    golden = _coo_canon(
        spgemm3d(PLUS_TIMES, A3, B3, tier="windowed", merge="sort")
    )
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        got = _coo_canon(
            spgemm3d(PLUS_TIMES, A3, B3, tier="windowed", merge="hash")
        )
        assert obs.registry.get_counter("spgemm.merge.hash_overflow") > 0
        # the fallback rerun resolved (and counted) the runs tier
        assert obs.registry.get_counter(
            "spgemm.merge.tier", tier="runs", source="hash_fallback",
            op="spgemm3d",
        ) == 1
    finally:
        obs.disable()
        obs.reset()
    _assert_same(golden, got, "hash fallback")


def test_piece_overflow_detected_and_diagnosed(rng):
    """Round-13 satellite: the fiber exchange's piece overflow is
    surfaced — the kernel reports the drop count and the sized entries
    raise naming the slack knob (plus the obs counter) instead of
    silently truncating downstream."""
    from combblas_tpu.parallel.mesh3d import (
        _check_fiber_overflow,
        summa3d_spgemm,
    )

    A3, B3 = _mats3d(rng)
    # deliberately starved piece capacity: the kernel must REPORT it
    _, overflow = summa3d_spgemm(
        PLUS_TIMES, A3, B3, flop_capacity=1 << 14,
        out_capacity=1 << 12, piece_capacity=1,
    )
    assert int(overflow[0]) > 0
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        with pytest.raises(ValueError, match="slack"):
            _check_fiber_overflow(
                int(overflow[0]), 1, "spgemm3d_windowed", 1.02
            )
        assert obs.registry.get_counter(
            "spgemm.summa3d.piece_overflow"
        ) == int(overflow[0])
    finally:
        obs.disable()
        obs.reset()


def test_forced_hash_on_generic_monoid_degrades(rng):
    """Review finding (r13): a forced ``merge="hash"`` on a semiring
    WITHOUT a native scatter combiner must degrade to ``runs`` where it
    is resolved — counted with a ``_degraded`` source — never assert
    mid-trace inside the shard_map body."""
    from combblas_tpu.semiring import Semiring

    sr = Semiring(
        name="plus_times_generic", add=lambda x, y: x + y,
        mul=lambda a, x: a * x, zero_fn=lambda dt: 0,
        one_fn=lambda dt: 1, add_kind="generic",
    )
    A3, B3 = _mats3d(rng)
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        spgemm3d(sr, A3, B3, tier="esc", merge="hash")
        assert obs.registry.get_counter(
            "spgemm.merge.tier", tier="runs", source="arg_degraded",
            op="spgemm3d",
        ) == 1
    finally:
        obs.disable()
        obs.reset()

