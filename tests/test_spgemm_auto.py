"""Auto-tiered SpGEMM: router, windowed kernel, support oracle, and the
distributed edge-harvest TC tier (ISSUE 3 tentpole).

Property contract: every tier is EXACT — ``spgemm_auto`` must agree with
the ESC golden across semirings, duplicate-entry COO inputs, empty-output
blocks, and forced-tier overrides (the MultTest golden-product pattern,
ReleaseTests/MultTest.cpp:122-234).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu import MAX_MIN, MIN_PLUS, PLUS_TIMES, obs
from combblas_tpu.ops.compressed import CSR, CSC
from combblas_tpu.ops.spgemm import (
    combine_hilo,
    dense_support_nnz,
    densify_combine,
    pack_support_bits,
    popcount_pair_counts,
    scatter_combine_for,
    spgemm_support_bits,
    support_window_counts,
)
from combblas_tpu.ops.tuples import SpTuples
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spgemm import (
    WINDOWED_MAX_COL_WINDOWS,
    WINDOWED_MAX_PANEL_CELLS,
    _pad128,
    bucket_plan_caps,
    choose_spgemm_tier,
    choose_tier_from_counts,
    default_block_cols,
    default_block_rows,
    dot_panel_feasible,
    panel_cap_from_bnnz,
    spgemm,
    spgemm_auto,
    spgemm_windowed,
    summa_rowblock_flops,
    summa_spgemm_windowed,
    summa_window_bnnz,
    summa_window_flops_pair,
    windowed_plan,
    windowed_plan_2d,
)
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.semiring import Semiring


def coo(rng, m, k, nnz, dup_frac=0.0):
    r = rng.integers(0, m, nnz).astype(np.int64)
    c = rng.integers(0, k, nnz).astype(np.int64)
    v = (rng.random(nnz) + 0.5).astype(np.float32)
    ndup = int(nnz * dup_frac)
    if ndup:
        r = np.concatenate([r, r[:ndup]])
        c = np.concatenate([c, c[:ndup]])
        v = np.concatenate([v, (rng.random(ndup) + 0.5).astype(np.float32)])
    return r, c, v


def dense_of(M: SpParMat) -> np.ndarray:
    """Host reconstruction; duplicate slots ADD (plus_times semantics) —
    only call on compacted products or plus_times inputs."""
    r, c, v, _ = jax.device_get((M.rows, M.cols, M.vals, M.nnz))
    out = np.zeros((M.nrows, M.ncols), np.float64)
    lr, lc = M.local_rows, M.local_cols
    for i in range(M.grid.pr):
        for j in range(M.grid.pc):
            m_ = r[i, j] < lr
            np.add.at(
                out,
                (r[i, j][m_] + i * lr, c[i, j][m_] + j * lc),
                v[i, j][m_],
            )
    return out


def host_nnz(M: SpParMat) -> int:
    return int(np.asarray(jax.device_get(M.getnnz())))


@pytest.mark.parametrize("srname", ["plus_times", "min_plus", "max_min"])
@pytest.mark.parametrize("p", [1, 2])
def test_windowed_matches_esc_across_semirings(rng, srname, p):
    """spgemm_auto(tier='windowed') == ESC, duplicate-entry COO input."""
    sr = {"plus_times": PLUS_TIMES, "min_plus": MIN_PLUS,
          "max_min": MAX_MIN}[srname]
    grid = Grid.make(p, p)
    m, k, n = 64, 48, 80
    ra, ca, va = coo(rng, m, k, 500, dup_frac=0.2)
    rb, cb, vb = coo(rng, k, n, 600, dup_frac=0.2)
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, k)
    B = SpParMat.from_global_coo(grid, rb, cb, vb, k, n)
    C_esc = spgemm(sr, A, B)
    C_win = spgemm_auto(sr, A, B, tier="windowed", block_rows=16)
    # both outputs are compacted/unique per cell: dense compare is exact
    np.testing.assert_allclose(
        dense_of(C_win), dense_of(C_esc), rtol=1e-5, atol=1e-6
    )
    assert host_nnz(C_win) == host_nnz(C_esc)


def test_windowed_exact_for_integer_counts(rng):
    """0/1 adjacency A²: counts are integers — bit-exact vs ESC."""
    grid = Grid.make(2, 2)
    m = 96
    ra, ca, _ = coo(rng, m, m, 900, dup_frac=0.1)
    ones = np.ones(len(ra), np.float32)
    A = SpParMat.from_global_coo(grid, ra, ca, ones, m, m)
    # ESC golden needs the DEDUPED input for 0/1 semantics
    key = np.unique(ra * m + ca)
    Au = SpParMat.from_global_coo(
        grid, key // m, key % m, np.ones(len(key), np.float32), m, m
    )
    C_esc = spgemm(PLUS_TIMES, Au, Au)
    C_win = spgemm_windowed(PLUS_TIMES, Au, Au, block_rows=16)
    np.testing.assert_array_equal(dense_of(C_win), dense_of(C_esc))
    assert host_nnz(C_win) == host_nnz(C_esc)


def test_empty_output_blocks_are_skipped(rng):
    """Rows with no A entries produce empty output blocks — the symbolic
    plan must mark them skipped, and the result still matches ESC."""
    grid = Grid.make(1, 1)
    m = 64
    # A entries confined to rows [0, 8): blocks 1..7 of 8 are empty
    ra = rng.integers(0, 8, 120).astype(np.int64)
    ca = rng.integers(0, m, 120).astype(np.int64)
    va = np.ones(120, np.float32)
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, m)
    rb, cb, vb = coo(rng, m, m, 400)
    B = SpParMat.from_global_coo(grid, rb, cb, vb, m, m)
    pb = np.asarray(
        jax.device_get(summa_rowblock_flops(A, B, 8, chunk_w=8))
    )
    pt = np.asarray(jax.device_get(summa_rowblock_flops(A, B, 8)))
    fc, oc, skip = windowed_plan(pb, pt, 8, A.local_rows, B.local_cols)
    assert skip[0] is False and all(skip[1:]), skip
    C_win, overflow = summa_spgemm_windowed(
        PLUS_TIMES, A, B, block_rows=8, flop_caps=fc, out_caps=oc,
        skip=skip, backend="scatter",
    )
    assert int(overflow) <= 0
    C_esc = spgemm(PLUS_TIMES, A, B)
    np.testing.assert_allclose(
        dense_of(C_win), dense_of(C_esc), rtol=1e-5, atol=1e-6
    )


def test_forced_tier_overrides_agree(rng):
    grid = Grid.make(2, 2)
    m = 48
    ra, ca, va = coo(rng, m, m, 300)
    # UNIQUE entries: the mxu tier densifies with the unique_indices
    # scatter contract (duplicate tolerance belongs to the esc/scan/
    # windowed tiers, covered above)
    key, idx = np.unique(ra * m + ca, return_index=True)
    ra, ca, va = ra[idx], ca[idx], va[idx]
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, m)
    ref = dense_of(spgemm(PLUS_TIMES, A, A))
    for tier in ("esc", "scan", "windowed", "mxu"):
        C = spgemm_auto(PLUS_TIMES, A, A, tier=tier, interpret=True)
        np.testing.assert_allclose(
            dense_of(C), ref, rtol=1e-4, atol=1e-5
        )


def test_tier_gate_rules():
    """The routing rule: mxu for small dense-kernel tiles; windowed only
    with a scatter combiner, bounded cells, and dense-enough output;
    scan otherwise."""
    generic = Semiring(
        name="generic_test", add=jnp.add, mul=jnp.multiply,
        zero_fn=lambda dt: 0, add_kind="generic",
    )
    assert scatter_combine_for(generic) is None
    # small tile + dense-kernel semiring → mxu
    assert choose_tier_from_counts(
        PLUS_TIMES, 4096, 4096 * 4096, 1, 1e6, "scatter"
    ) == "mxu"
    # big tile, dense output, scatter combiner → windowed
    assert choose_tier_from_counts(
        PLUS_TIMES, 1 << 16, 1 << 32, 1, 1e9, "scatter"
    ) == "windowed"
    # generic monoid cannot scatter → scan
    assert choose_tier_from_counts(
        generic, 1 << 16, 1 << 32, 1, 1e9, "scatter"
    ) == "scan"
    # output too sparse relative to the dense tile → scan
    assert choose_tier_from_counts(
        PLUS_TIMES, 1 << 20, 1 << 33, 1, 1e3, "scatter"
    ) == "scan"
    # ISSUE 5: the dot backend now has the 2D B-column-windowed
    # formulation — mid-scale tiles above the mxu envelope route to
    # windowed on TPU too (this exact case returned "scan" before)
    assert choose_tier_from_counts(
        PLUS_TIMES, 1 << 16, 1 << 32, 1, 1e9, "dot", k_dim=1 << 16
    ) == "windowed"
    # ...but not when even a minimum 512-wide B panel would exceed the
    # stage-operand envelope
    assert choose_tier_from_counts(
        PLUS_TIMES, 1 << 20, 1 << 33, 1, 1e9, "dot", k_dim=1 << 20
    ) == "scan"
    # tropical semirings ride the same dot rung (Pallas dense kernel)
    assert choose_tier_from_counts(
        MIN_PLUS, 1 << 16, 1 << 32, 1, 1e9, "dot", k_dim=1 << 16
    ) == "windowed"
    # generic monoid cannot densify-combine → scan even on dot
    assert choose_tier_from_counts(
        generic, 1 << 16, 1 << 32, 1, 1e9, "dot", k_dim=1 << 16
    ) == "scan"
    # allow_mxu=False (the duplicate-entry fallback) re-evaluates the
    # rest of the ladder
    assert choose_tier_from_counts(
        PLUS_TIMES, 4096, 4096 * 4096, 1, 1e7, "scatter",
        allow_mxu=False,
    ) == "windowed"


def test_router_records_obs_counters(rng):
    grid = Grid.make(1, 1)
    m = 48
    ra, ca, va = coo(rng, m, m, 300)
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, m)
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        spgemm_auto(PLUS_TIMES, A, A, tier="windowed", block_rows=16)
        assert obs.registry.get_counter(
            "spgemm.auto.tier", tier="windowed", sr="plus_times"
        ) == 1
        assert obs.registry.get_gauge("spgemm.windowed.blocks") == 3
        assert obs.registry.get_counter(
            "spgemm.windowed.windows_skipped"
        ) >= 0
        assert obs.registry.get_gauge("spgemm.auto.mask_density") > 0
    finally:
        obs.disable()
        obs.reset()


def test_support_oracle_exact(rng):
    da = (rng.random((50, 40)) < 0.2).astype(np.float32)
    db = (rng.random((40, 60)) < 0.2).astype(np.float32)
    a = SpTuples.from_dense(da, capacity=600)
    b = SpTuples.from_dense(db, capacity=600)
    bits, row_nnz = spgemm_support_bits(a, b, row_block=16)
    P = (da @ db) > 0
    got = np.zeros_like(P)
    bb = np.asarray(bits)
    for j in range(60):
        got[:, j] = (bb[:, j >> 5] >> (j & 31)) & 1
    np.testing.assert_array_equal(got, P)
    np.testing.assert_array_equal(np.asarray(row_nnz), P.sum(1))
    # masked numeric pass over the support: popcount counts == A·B values
    ii, jj = np.nonzero(P)
    chunk = 64
    pad = -(-len(ii) // chunk) * chunk
    iiP = np.pad(ii, (0, pad - len(ii))).astype(np.int32)
    jjP = np.pad(jj, (0, pad - len(jj))).astype(np.int32)
    w = np.pad(np.ones(len(ii), np.int32), (0, pad - len(ii)))
    abits = pack_support_bits(a.rows, a.cols, 50, 40)
    btbits = CSC.from_tuples(b).to_bitmask()
    hilo = popcount_pair_counts(
        abits, btbits, jnp.asarray(iiP), jnp.asarray(jjP),
        jnp.asarray(w), chunk=chunk,
    )
    assert combine_hilo(hilo) == int((da @ db)[ii, jj].sum())


def test_pack_support_bits_dedups(rng):
    m, n = 37, 70
    r = rng.integers(0, m, 200).astype(np.int32)
    c = rng.integers(0, n, 200).astype(np.int32)
    r = np.concatenate([r, r[:50]])
    c = np.concatenate([c, c[:50]])  # hard duplicates: would carry bits
    bits = pack_support_bits(jnp.asarray(r), jnp.asarray(c), m, n)
    ref = np.zeros((m, n), bool)
    ref[r, c] = True
    bb = np.asarray(bits)
    got = np.zeros((m, n), bool)
    for j in range(n):
        got[:, j] = (bb[:, j >> 5] >> (j & 31)) & 1
    np.testing.assert_array_equal(got, ref)


def test_csr_csc_bitmask_views(rng):
    d = (rng.random((20, 45)) < 0.25).astype(np.float32)
    t = SpTuples.from_dense(d, capacity=300)
    rb = np.asarray(CSR.from_tuples(t).to_bitmask())
    cb = np.asarray(CSC.from_tuples(t).to_bitmask())
    for i in range(20):
        for j in range(45):
            assert bool((rb[i, j >> 5] >> (j & 31)) & 1) == bool(d[i, j])
            assert bool((cb[j, i >> 5] >> (i & 31)) & 1) == bool(d[i, j])


def test_dense_support_nnz_padding(rng):
    d = (rng.random((32, 48)) < 0.3).astype(np.float32)
    assert int(dense_support_nnz(jnp.asarray(d), 0.0, 30, 40)) == int(
        (d[:30, :40] != 0).sum()
    )


def test_distributed_edge_harvest_tc_matches_masked(rng):
    """ISSUE 3 satellite: distributed bit-packed edge-harvest TC vs the
    masked-SpGEMM count (the sparse path), duplicate entries included."""
    from combblas_tpu.models.tc import triangle_count

    # n chosen so local_cols (n/2 on the 2x2 grid) is a multiple of 32 —
    # the distributed tier's word-aligned tile-concat requirement
    n = 128
    m = rng.random((n, n)) < 0.08
    m = np.triu(m, 1)
    m = m | m.T
    r0, c0 = np.nonzero(m)
    dup = rng.choice(len(r0), 30)
    r = np.concatenate([r0, r0[dup]])
    c = np.concatenate([c0, c0[dup]])
    grid = Grid.make(2, 2)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    Au = SpParMat.from_global_coo(
        grid, r0, c0, np.ones(len(r0), np.float32), n, n
    )
    want = triangle_count(Au, kernel="sparse")  # masked-SpGEMM count
    assert triangle_count(A, kernel="edgeharvest") == want
    assert triangle_count(A) == want  # auto routes to the tier
    ref = int(np.trace(np.linalg.matrix_power(m.astype(np.int64), 3)) // 6)
    assert want == ref


def test_distributed_edge_harvest_tiles_loop_their_own_steps(rng):
    """Each tile's scan runs the steps its OWN kept pairs fill, inside
    one ``shard_map``: on a 2x2 mesh the tile above the diagonal keeps
    nothing (0 steps), a diagonal tile about half its slots (k) and the
    tile below every one (2k); a small ``chunk`` makes k several steps.
    The count is the masked SpGEMM's and the definition's."""
    from combblas_tpu.models.tc import _tc_edge_harvest_dist, triangle_count

    n, chunk = 128, 32
    m = rng.random((n, n)) < 0.12
    m = np.triu(m, 1)
    m = m | m.T
    r, c = np.nonzero(m)
    grid = Grid.make(2, 2)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    h = n // 2
    kept = {(i, j): int(np.tril(m, -1)[i * h:(i + 1) * h,
                                      j * h:(j + 1) * h].sum())
            for i in (0, 1) for j in (0, 1)}
    steps = {t: -(-k // chunk) for t, k in kept.items()}
    assert steps[0, 1] == 0 < steps[0, 0] < steps[1, 0]
    assert steps[1, 0] >= 2 * min(steps[0, 0], steps[1, 1]) - 1 > 4
    ref = int(np.trace(np.linalg.matrix_power(m.astype(np.int64), 3)) // 6)
    assert combine_hilo(_tc_edge_harvest_dist(A, chunk=chunk)) == 3 * ref > 0
    assert triangle_count(A, kernel="sparse") == ref


def test_distributed_edge_harvest_tc_ceil_blocked(rng):
    """n % local_rows != 0 (ceil-blocking over-cover): the n-sentinel
    minus the last block's offset lands INSIDE the local range — the
    kernel must drop padded/dup/loop slots explicitly, not by sentinel
    arithmetic (regression: corrupted bitmask via scatter-add carry)."""
    from combblas_tpu.models.tc import triangle_count

    n = 127  # 2x2 grid → lr = lc = 64 (word-aligned), p*lr = 128 > n
    m = rng.random((n, n)) < 0.1
    m = np.triu(m, 1)
    m = m | m.T
    r0, c0 = np.nonzero(m)
    # duplicates AND a self-loop stored on the last grid row
    r = np.concatenate([r0, r0[:20], [n - 1]])
    c = np.concatenate([c0, c0[:20], [n - 1]])
    grid = Grid.make(2, 2)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), n, n
    )
    ref = int(np.trace(np.linalg.matrix_power(m.astype(np.int64), 3)) // 6)
    assert triangle_count(A, kernel="edgeharvest") == ref


def test_default_block_rows_bounds():
    br = default_block_rows(1 << 16, 1 << 16)
    assert 1 <= br <= 1 << 16
    assert -(-(1 << 16) // br) <= 33  # ~WINDOWED_MAX_BLOCKS programs
    assert default_block_rows(5, 7) >= 5  # tiny tiles: one block


# --- 2D B-column-windowed dot backend (ISSUE 5 tentpole) --------------------


@pytest.mark.parametrize(
    "p,srname",
    [
        (1, "plus_times"),
        (1, "min_plus"),
        # (1, max_min) joined the slow set in round 12 (tier-1 budget):
        # same single-device tropical dot2d path as (1, min_plus)
        pytest.param(1, "max_min", marks=pytest.mark.slow),
        (2, "plus_times"),
        # the distributed tropical (Pallas-matmul) cases cost ~20 s each
        # on the 1-core mesh; the tropical dot2d path stays tier-1 at
        # p=1 and the 2x2 fused kernel at plus_times, so these two run
        # under -m slow
        pytest.param(2, "min_plus", marks=pytest.mark.slow),
        pytest.param(2, "max_min", marks=pytest.mark.slow),
    ],
)
def test_windowed_dot_2d_matches_esc_across_semirings(rng, srname, p):
    """Forced dot-backend 2D windowed == ESC golden across semirings,
    DUPLICATE-ENTRY COO inputs included: ``densify_combine`` folds
    repeats with the semiring combiner, so the dot backend no longer
    carries the mxu tier's unique-entries precondition.  p=1 exercises
    the per-block local fast path, p=2 the fused shard_map kernel."""
    sr = {"plus_times": PLUS_TIMES, "min_plus": MIN_PLUS,
          "max_min": MAX_MIN}[srname]
    grid = Grid.make(p, p)
    m, k, n = 64, 48, 80
    ra, ca, va = coo(rng, m, k, 500, dup_frac=0.2)
    rb, cb, vb = coo(rng, k, n, 600, dup_frac=0.2)
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, k)
    B = SpParMat.from_global_coo(grid, rb, cb, vb, k, n)
    C_esc = spgemm(sr, A, B)
    C_win = spgemm_auto(
        sr, A, B, tier="windowed", backend="dot",
        block_rows=16, block_cols=32, interpret=True,
    )
    np.testing.assert_allclose(
        dense_of(C_win), dense_of(C_esc), rtol=1e-5, atol=1e-6
    )
    assert host_nnz(C_win) == host_nnz(C_esc)


def test_windowed_dot_2d_empty_windows_skipped(rng):
    """A confined to rows [0, 8), B confined to cols [0, 16): every 2D
    window except (0, 0) is symbolically empty — the plan must skip
    them (never densified, never matmul'd, never scanned) and the
    result still matches ESC."""
    grid = Grid.make(1, 1)
    m = 64
    ra = rng.integers(0, 8, 120).astype(np.int64)
    ca = rng.integers(0, m, 120).astype(np.int64)
    A = SpParMat.from_global_coo(
        grid, ra, ca, np.ones(120, np.float32), m, m
    )
    rb = rng.integers(0, m, 200).astype(np.int64)
    cb = rng.integers(0, 16, 200).astype(np.int64)
    B = SpParMat.from_global_coo(
        grid, rb, cb, np.ones(200, np.float32), m, m
    )
    pair = np.asarray(
        jax.device_get(summa_window_flops_pair(A, B, 8, 16, chunk_w=8))
    )
    fc, oc, skip = windowed_plan_2d(pair[0], pair[1], 8, 16, m, m)
    assert not skip[0][0]
    assert all(
        skip[g][h]
        for g in range(8) for h in range(4) if (g, h) != (0, 0)
    ), skip
    panel_cap = panel_cap_from_bnnz(
        jax.device_get(summa_window_bnnz(B, 16)), int(B.capacity)
    )
    C_win, overflow = summa_spgemm_windowed(
        PLUS_TIMES, A, B, block_rows=8, flop_caps=fc, out_caps=oc,
        skip=skip, backend="dot", block_cols=16, panel_cap=panel_cap,
    )
    assert int(overflow) <= 0
    C_esc = spgemm(PLUS_TIMES, A, B)
    np.testing.assert_allclose(
        dense_of(C_win), dense_of(C_esc), rtol=1e-5, atol=1e-6
    )


def test_densify_combine_absorbs_duplicates(rng):
    """densify_combine == dedup-then-densify under each combiner."""
    m, n = 20, 30
    r = rng.integers(0, m, 80).astype(np.int32)
    c = rng.integers(0, n, 80).astype(np.int32)
    v = (rng.random(80) + 0.5).astype(np.float32)
    r = np.concatenate([r, r[:30]])
    c = np.concatenate([c, c[:30]])
    v = np.concatenate([v, (rng.random(30) + 0.5).astype(np.float32)])
    t = SpTuples.from_coo(r, c, v, m, n, capacity=128)
    for sr, fold, init in (
        (PLUS_TIMES, np.add, 0.0),
        (MIN_PLUS, np.minimum, np.inf),
        (MAX_MIN, np.maximum, -np.inf),
    ):
        ref = np.full((32, 32), init, np.float32)
        for ri, ci, vi in zip(r, c, v):
            ref[ri, ci] = fold(ref[ri, ci], vi)
        got = np.asarray(jax.device_get(densify_combine(sr, t, 32, 32)))
        np.testing.assert_allclose(got, ref, rtol=1e-6)


def test_mxu_unique_precondition_guard(rng):
    """ISSUE 5 satellite: the router detects duplicate-entry tiles and
    demotes mxu to a duplicate-absorbing rung instead of silently
    producing wrong results; ``assume_unique`` skips the check."""
    grid = Grid.make(1, 1)
    m = 48
    ra, ca, va = coo(rng, m, m, 300, dup_frac=0.2)  # repeats guaranteed
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, m)
    tier = choose_spgemm_tier(PLUS_TIMES, A, A, backend="scatter")
    assert tier in ("windowed", "scan")
    assert choose_spgemm_tier(
        PLUS_TIMES, A, A, backend="scatter", assume_unique=True
    ) == "mxu"
    # unique input still routes mxu
    key, idx = np.unique(ra * m + ca, return_index=True)
    Au = SpParMat.from_global_coo(
        grid, ra[idx], ca[idx], va[idx], m, m
    )
    assert choose_spgemm_tier(
        PLUS_TIMES, Au, Au, backend="scatter"
    ) == "mxu"
    # the auto-routed product on the duplicate input stays EXACT (the
    # fallback rung absorbs repeats), and the demotion is observable
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        C = spgemm_auto(PLUS_TIMES, A, A, backend="scatter")
        assert obs.registry.get_counter(
            "spgemm.auto.dedup_fallback", sr="plus_times"
        ) == 1
        assert obs.registry.get_counter(
            "spgemm.auto.tier", tier="mxu", sr="plus_times"
        ) == 0
    finally:
        obs.disable()
        obs.reset()
    ref = spgemm(PLUS_TIMES, A, A)
    np.testing.assert_allclose(
        dense_of(C), dense_of(ref), rtol=1e-5, atol=1e-6
    )


def test_router_routes_midscale_to_windowed_dot(rng, monkeypatch):
    """ISSUE 5 acceptance: a product whose B tile exceeds the mxu
    envelope auto-selects windowed with backend='dot' (it fell through
    to scan before), and the 2D run bounds the stage operand by the
    column window (panel_cells gauge ≤ envelope) while agreeing with
    the ESC golden."""
    import combblas_tpu.parallel.spgemm as psp

    # shrink the mxu envelope so a 96-dim tile is "mid-scale" for the
    # test (the real envelope needs scale-14 tiles — benchmark turf)
    monkeypatch.setattr(psp, "MXU_MAX_TILE_DIM", 32)
    grid = Grid.make(1, 1)
    m = 96
    ra, ca, va = coo(rng, m, m, 2000)
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, m)
    assert psp.choose_spgemm_tier(
        PLUS_TIMES, A, A, backend="dot"
    ) == "windowed"
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        C = spgemm_auto(
            PLUS_TIMES, A, A, backend="dot", block_rows=32,
            block_cols=32,
        )
        assert obs.registry.get_counter(
            "spgemm.auto.tier", tier="windowed", sr="plus_times"
        ) == 1
        panel_cells = obs.registry.get_gauge(
            "spgemm.windowed.panel_cells"
        )
        assert panel_cells == _pad128(m) * _pad128(32)
        assert panel_cells <= WINDOWED_MAX_PANEL_CELLS
        assert obs.registry.get_gauge(
            "spgemm.windowed.col_windows"
        ) == 3
        assert obs.registry.get_counter(
            "spgemm.windowed.col_windows_skipped"
        ) >= 0
        assert obs.registry.get_gauge(
            "spgemm.windowed.window_density"
        ) > 0
    finally:
        obs.disable()
        obs.reset()
    ref = spgemm(PLUS_TIMES, A, A)
    np.testing.assert_allclose(
        dense_of(C), dense_of(ref), rtol=1e-5, atol=1e-6
    )


def test_windowed_dot_panel_envelope():
    """The stage-operand memory bound: default_block_cols keeps one
    dense B panel within WINDOWED_MAX_PANEL_CELLS and the unrolled
    window count bounded; at mid scale the panel is a strict fraction
    of B's full dense tile width (the quantity that used to force the
    router to scan on TPU)."""
    for lrb, lcb in [(1 << 16, 1 << 16), (16384, 16384), (8192, 65536)]:
        bc = default_block_cols(lrb, lcb)
        pk, pwin = _pad128(lrb), _pad128(bc)
        assert 1 <= bc <= max(lcb, 1)
        assert -(-lcb // bc) <= WINDOWED_MAX_COL_WINDOWS
        if pk * 512 <= WINDOWED_MAX_PANEL_CELLS:
            assert pk * pwin <= WINDOWED_MAX_PANEL_CELLS, (lrb, lcb)
    # scale-16 square tile: the panel is ≥16x narrower than dense B
    bc = default_block_cols(1 << 16, 1 << 16)
    assert _pad128(bc) * 16 <= _pad128(1 << 16)
    # tiny tiles degenerate to one window
    assert default_block_cols(64, 80) == 80
    # extreme region pad(k)·lcB > 32·PANEL: the window-count floor
    # would exceed the envelope, so the router gates it to scan (only
    # forced calls may trade memory for program size there)
    assert not dot_panel_feasible(1 << 17, 1 << 16)
    assert dot_panel_feasible(1 << 17)  # a 512-wide window alone fits
    assert choose_tier_from_counts(
        PLUS_TIMES, 1 << 17, (1 << 17) * (1 << 16), 1, 1e12, "dot",
        k_dim=1 << 17, n_dim=1 << 16,
    ) == "scan"


# --- round 9: pipelined carousel, packed launches, 3D windowed --------------


@pytest.mark.parametrize("srname", ["plus_times", "min_plus", "max_min"])
def test_pipelined_carousel_matches_unpipelined(rng, srname):
    """ISSUE 7 satellite: the stage-pipelined windowed carousel
    (ring=True, pipeline=True) and the serial-chain control
    (pipeline=False) both agree exactly with the ESC golden on a 2x2
    grid with DUPLICATE-entry COO input — the overlap restructure is a
    schedule change, never a semantics change."""
    from combblas_tpu.parallel.spgemm import spgemm_windowed

    sr = {"plus_times": PLUS_TIMES, "min_plus": MIN_PLUS,
          "max_min": MAX_MIN}[srname]
    grid = Grid.make(2, 2)
    m, k, n = 64, 48, 80
    ra, ca, va = coo(rng, m, k, 500, dup_frac=0.2)
    rb, cb, vb = coo(rng, k, n, 600, dup_frac=0.2)
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, k)
    B = SpParMat.from_global_coo(grid, rb, cb, vb, k, n)
    ref = dense_of(spgemm(sr, A, B))
    for pipe in (True, False):
        C = spgemm_windowed(
            sr, A, B, block_rows=16, backend="scatter",
            ring=True, pipeline=pipe,
        )
        np.testing.assert_allclose(
            dense_of(C), ref, rtol=1e-5, atol=1e-6
        )


def test_pipelined_carousel_dot2d_and_esc_ring(rng):
    """The carousel restructure covers every ring path: the 2D dot
    windowed carousel and the (now pipelined) ESC ring both match the
    gathered-schedule golden."""
    from combblas_tpu.parallel.spgemm import (
        spgemm_windowed,
        summa_capacities,
        summa_spgemm,
    )

    grid = Grid.make(2, 2)
    m = 96
    ra, ca, va = coo(rng, m, m, 800, dup_frac=0.15)
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, m)
    ref = dense_of(spgemm(PLUS_TIMES, A, A))
    for pipe in (True, False):
        C = spgemm_windowed(
            PLUS_TIMES, A, A, block_rows=16, block_cols=32,
            backend="dot", ring=True, pipeline=pipe,
        )
        np.testing.assert_allclose(
            dense_of(C), ref, rtol=1e-5, atol=1e-6
        )
    fcap, ocap = summa_capacities(A, A)
    C = summa_spgemm(
        PLUS_TIMES, A, A, flop_capacity=fcap, out_capacity=ocap,
        ring=True,
    )
    np.testing.assert_allclose(dense_of(C), ref, rtol=1e-5, atol=1e-6)


def test_packed_plan_equals_skiplist(rng):
    """ISSUE 7 satellite: the packed launch list is exactly the
    complement of the skip list, and a packed (skip-listed) run emits
    the SAME output as the full-grid run with no skips — packing elides
    launches, never results."""
    from combblas_tpu.parallel.spgemm import (
        _live_windows_by_block,
        packed_windows,
        packed_windows_2d,
        panel_cap_from_bnnz,
        summa_window_bnnz,
        summa_window_flops_pair,
    )

    grid = Grid.make(1, 1)
    m = 64
    # A confined to rows [0, 24): the lower row blocks are empty
    ra = rng.integers(0, 24, 200).astype(np.int64)
    ca = rng.integers(0, m, 200).astype(np.int64)
    A = SpParMat.from_global_coo(
        grid, ra, ca, np.ones(200, np.float32), m, m
    )
    rb = rng.integers(0, m, 300).astype(np.int64)
    cb = rng.integers(0, 32, 300).astype(np.int64)  # right windows empty
    B = SpParMat.from_global_coo(
        grid, rb, cb, np.ones(300, np.float32), m, m
    )
    pair = np.asarray(
        jax.device_get(summa_window_flops_pair(A, B, 8, 16, chunk_w=8))
    )
    fc, oc, skip = windowed_plan_2d(pair[0], pair[1], 8, 16, m, m)
    pairs = packed_windows_2d(skip)
    # the packed list IS the complement of the skip list, in kernel order
    assert pairs == tuple(
        (g, h) for g in range(len(skip)) for h in range(len(skip[0]))
        if not skip[g][h]
    )
    assert 0 < len(pairs) < len(skip) * len(skip[0])
    assert packed_windows(tuple(all(row) for row in skip)) == tuple(
        g for g, hs in _live_windows_by_block(skip)
    )
    panel_cap = panel_cap_from_bnnz(
        jax.device_get(summa_window_bnnz(B, 16)), int(B.capacity)
    )
    no_skip = tuple((False,) * len(row) for row in skip)
    outs = {}
    for name, sk in (("packed", skip), ("full", no_skip)):
        C, overflow = summa_spgemm_windowed(
            PLUS_TIMES, A, B, block_rows=8, flop_caps=fc, out_caps=oc,
            skip=sk, backend="dot", block_cols=16, panel_cap=panel_cap,
        )
        assert int(overflow) <= 0
        outs[name] = dense_of(C)
    np.testing.assert_array_equal(outs["packed"], outs["full"])
    np.testing.assert_allclose(
        outs["packed"], dense_of(spgemm(PLUS_TIMES, A, B)),
        rtol=1e-5, atol=1e-6,
    )


def test_blocked_dispatch_matches_fused(rng):
    """ISSUE 7: the blocked-dispatch distributed windowed tier (one
    small shard_map program per occupied row block — the live-set
    bound that fits scale-18 tiles in RAM) emits the same result as
    the fused kernel and the ESC golden, duplicate entries included."""
    from combblas_tpu.parallel.spgemm import (
        WINDOWED_CHUNK_W,
        summa_spgemm_windowed_blocked,
    )

    grid = Grid.make(2, 2)
    m = 96
    ra, ca, va = coo(rng, m, m, 800, dup_frac=0.15)
    # rows confined to [0, 32): the trailing row blocks are empty on
    # EVERY grid row, so the packed host loop's skip path is exercised
    ra = ra % 32
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, m)
    pb, pt = (
        np.asarray(jax.device_get(
            summa_rowblock_flops(A, A, 16, chunk_w=w)))
        for w in (WINDOWED_CHUNK_W, 0)
    )
    fc, oc, skip = windowed_plan(pb, pt, 16, A.local_rows, A.local_cols)
    assert any(skip)
    C, over = summa_spgemm_windowed_blocked(
        PLUS_TIMES, A, A, block_rows=16, flop_caps=fc, out_caps=oc,
        skip=skip, chunk_w=WINDOWED_CHUNK_W,
    )
    assert int(over) <= 0
    C_f, over_f = summa_spgemm_windowed(
        PLUS_TIMES, A, A, block_rows=16, flop_caps=fc, out_caps=oc,
        skip=skip, backend="scatter", chunk_w=WINDOWED_CHUNK_W,
    )
    assert int(over_f) <= 0
    np.testing.assert_array_equal(dense_of(C), dense_of(C_f))
    np.testing.assert_allclose(
        dense_of(C), dense_of(spgemm(PLUS_TIMES, A, A)),
        rtol=1e-5, atol=1e-6,
    )
    assert host_nnz(C) == host_nnz(C_f)


@pytest.mark.parametrize("backend", [
    "dot",
    # the scatter backend re-runs the whole 2D->3D->2D route for a
    # second accumulate kernel (~6 s of compiles); the dot case keeps
    # the routing/conversion coverage in tier-1 (round 17 budget) and
    # scatter-vs-dot agreement rides the 2D/3D kernel suites
    pytest.param("scatter", marks=pytest.mark.slow),
])
def test_spgemm_auto_3d_matches_2d(rng, backend):
    """ISSUE 7 satellite: the windowed3d route (2D → layered 3D mesh →
    per-layer windowed SUMMA → fiber reduce → back to 2D) agrees
    BIT-EXACTLY with the 2D spgemm_auto product on the 8-device mesh
    (0/1 adjacency counts are integers)."""
    from combblas_tpu.parallel.mesh3d import Grid3D

    grid = Grid.make(2, 2)
    g3 = Grid3D.make(2, 2, 2)
    m = 64
    ra, ca, _ = coo(rng, m, m, 900, dup_frac=0.1)
    A = SpParMat.from_global_coo(
        grid, ra, ca, np.ones(len(ra), np.float32), m, m
    )
    ref = spgemm_auto(PLUS_TIMES, A, A, tier="windowed", block_rows=16)
    C = spgemm_auto(
        PLUS_TIMES, A, A, tier="windowed3d", grid3=g3,
        backend=backend, block_rows=16, block_cols=16,
    )
    np.testing.assert_array_equal(dense_of(C), dense_of(ref))
    assert host_nnz(C) == host_nnz(ref)


def test_router_upgrades_windowed_to_3d(rng, monkeypatch):
    """choose_spgemm_tier upgrades a 2D-windowed-bound product to
    windowed3d when a COMPATIBLE layered mesh is offered — and keeps
    the 2D tier when the layout does not divide over the layers."""
    import combblas_tpu.parallel.spgemm as psp
    from combblas_tpu.parallel.mesh3d import Grid3D, summa3d_compatible

    monkeypatch.setattr(psp, "MXU_MAX_TILE_DIM", 32)
    grid = Grid.make(1, 1)
    m = 96
    ra, ca, va = coo(rng, m, m, 2000)
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, m)
    g3 = Grid3D.make(2, 2, 2)
    assert psp.choose_spgemm_tier(
        PLUS_TIMES, A, A, backend="scatter"
    ) == "windowed"
    assert psp.choose_spgemm_tier(
        PLUS_TIMES, A, A, backend="scatter", grid3=g3
    ) == "windowed3d"
    # an odd dimension cannot col-split over 2 layers: router stays 2D
    assert not summa3d_compatible(g3, 98, 98, 98)
    ra2 = np.minimum(ra, 97)
    ca2 = np.minimum(ca, 97)
    A2 = SpParMat.from_global_coo(grid, ra2, ca2, va, 98, 98)
    assert psp.choose_spgemm_tier(
        PLUS_TIMES, A2, A2, backend="scatter", grid3=g3
    ) == "windowed"


def test_support_oracle_window_counts_and_seeding(rng):
    """``support_window_counts`` returns the exact per-window output
    nnz, and ``spgemm_windowed(oracle=True)`` (dot backend) stays exact
    with the tightened caps."""
    da = (rng.random((64, 48)) < 0.15).astype(np.float32)
    db = (rng.random((48, 64)) < 0.15).astype(np.float32)
    a = SpTuples.from_dense(da, capacity=600)
    b = SpTuples.from_dense(db, capacity=600)
    bits, _ = spgemm_support_bits(a, b, row_block=16)
    cnt = np.asarray(
        jax.device_get(support_window_counts(bits, 16, 32, 64, 64))
    )
    P = (da @ db) > 0
    for g in range(4):
        for h in range(2):
            want = int(
                P[g * 16:(g + 1) * 16, h * 32:(h + 1) * 32].sum()
            )
            assert cnt[g, h] == want, (g, h)
    grid = Grid.make(1, 1)
    m = 64
    ra, ca, va = coo(rng, m, m, 700)
    A = SpParMat.from_global_coo(grid, ra, ca, va, m, m)
    ref = spgemm(PLUS_TIMES, A, A)
    C = spgemm_windowed(
        PLUS_TIMES, A, A, block_rows=32, block_cols=32, backend="dot",
        oracle=True,
    )
    np.testing.assert_allclose(
        dense_of(C), dense_of(ref), rtol=1e-5, atol=1e-6
    )
    assert host_nnz(C) == host_nnz(ref)


# --- building-block dispatch / bucketed caps -------------------------------


def test_ring_wins_over_explicit_blocked(rng):
    """ring is a fused-only schedule: an explicit dispatch='blocked'
    yields to it (obs-counted), instead of silently dropping the
    carousel request."""
    grid = Grid.make(2, 2)
    m = 64
    r, c, v = coo(rng, m, m, 400, dup_frac=0.2)
    A = SpParMat.from_global_coo(grid, r, c, v, m, m)
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        spgemm_windowed(
            PLUS_TIMES, A, A, block_rows=8, backend="scatter",
            ring=True, dispatch="blocked",
        )
        assert obs.registry.get_counter(
            "spgemm.windowed.dispatch_conflict"
        ) == 1
        assert obs.registry.get_counter(
            "spgemm.windowed.dispatch", mode="fused"
        ) == 1
    finally:
        obs.disable()
        obs.reset()


def test_bucket_plan_caps_shapes():
    fc, oc = bucket_plan_caps((3, 17, 1), (1000, 5, 64))
    assert fc == (4, 32, 1) and oc == (1024, 8, 64)
    fc2, oc2 = bucket_plan_caps(
        ((3, 5), (9, 1)), ((33, 2), (7, 128))
    )
    assert fc2 == ((4, 8), (16, 1)) and oc2 == ((64, 2), (8, 128))


@pytest.mark.parametrize("dispatch", [
    "auto", "blocked",
    # "fused" is slow-lane (round 12, tier-1 budget): the fused
    # one-graph kernel keeps tier-1 coverage via the ring tests and
    # test_blocked_dispatch_matches_fused
    pytest.param("fused", marks=pytest.mark.slow),
])
def test_windowed_dispatch_agreement(rng, dispatch):
    """The blocked building-block dispatch (the round-10 multi-device
    default) emits the same product as the fused graph."""
    grid = Grid.make(2, 2)
    m = 96
    r, c, v = coo(rng, m, m, 800, dup_frac=0.1)
    A = SpParMat.from_global_coo(grid, r, c, v, m, m)
    C = spgemm_windowed(
        PLUS_TIMES, A, A, block_rows=8, backend="scatter",
        dispatch=dispatch,
    )
    C_ref = spgemm(PLUS_TIMES, A, A)
    np.testing.assert_allclose(
        dense_of(C), dense_of(C_ref), rtol=1e-5, atol=1e-6
    )


def test_windowed_auto_dispatch_is_blocked_multidev(rng):
    grid = Grid.make(2, 2)
    m = 96
    r, c, v = coo(rng, m, m, 800, dup_frac=0.2)
    A = SpParMat.from_global_coo(grid, r, c, v, m, m)
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        spgemm_windowed(PLUS_TIMES, A, A, block_rows=8,
                        backend="scatter")
        assert obs.registry.get_counter(
            "spgemm.windowed.dispatch", mode="blocked"
        ) == 1
        # ring keeps the fused carousel (the pipelined schedule)
        obs.reset()
        spgemm_windowed(PLUS_TIMES, A, A, block_rows=8,
                        backend="scatter", ring=True)
        assert obs.registry.get_counter(
            "spgemm.windowed.dispatch", mode="fused"
        ) == 1
    finally:
        obs.disable()
        obs.reset()
