"""Round-10 autotuner: plan-store persistence + robustness, probe
determinism, store-routed vs heuristic-routed agreement, serve lane
replay, and the shared cache health surface (docs/autotuning.md).

The store contract under test: remembered plans make routing
reproducible across processes, a damaged plans file NEVER takes the
library down (fall back to the next precedence rung, counter bumped),
and store-routed products are bit-exact with heuristic-routed ones —
the store only chooses among exact kernels.
"""

import json
import os

import jax
import numpy as np
import pytest

from combblas_tpu import MAX_MIN, MIN_PLUS, PLUS_TIMES, obs
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spgemm import (
    bucket_plan_caps,
    spgemm,
    spgemm_auto,
    spgemm_windowed,
)
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.tuner import (
    PlanKey,
    PlanRecord,
    PlanStore,
    SCHEMA,
    config,
    density_band,
    plan_key_from_counts,
    shape_bucket,
    spgemm_plan_key,
)
from combblas_tpu.tuner import store as tstore
from combblas_tpu.tuner.probe import downsample_coo, probe_spgemm

SRS = {"plus_times": PLUS_TIMES, "min_plus": MIN_PLUS,
       "max_min": MAX_MIN}


def coo(rng, m, k, nnz, dup_frac=0.2):
    r = rng.integers(0, m, nnz).astype(np.int64)
    c = rng.integers(0, k, nnz).astype(np.int64)
    v = (rng.random(nnz) + 0.5).astype(np.float32)
    ndup = int(nnz * dup_frac)
    if ndup:
        r = np.concatenate([r, r[:ndup]])
        c = np.concatenate([c, c[:ndup]])
        v = np.concatenate(
            [v, (rng.random(ndup) + 0.5).astype(np.float32)]
        )
    return r, c, v


def dense_of(M: SpParMat) -> np.ndarray:
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


def _use_store(monkeypatch, path) -> PlanStore:
    """Point the process store at ``path`` and return the instance."""
    monkeypatch.setenv(config.ENV_PLAN_STORE, str(path))
    tstore._reset_for_tests()
    st = tstore.get_store()
    assert st is not None and st.path == os.path.abspath(str(path))
    return st


@pytest.fixture(autouse=True)
def _fresh_singleton():
    """Each test resolves its own store; drop the cached instance on
    both sides so cross-test state cannot leak through the singleton."""
    tstore._reset_for_tests()
    yield
    tstore._reset_for_tests()


def _key(op="spgemm", sr="plus_times", backend="scatter",
         grid="1x1") -> PlanKey:
    return plan_key_from_counts(
        sr, 1 << 14, 1 << 14, 1 << 14, 131072, 131072, backend, grid,
        op=op, platform="cpu",
    )


# --- store persistence + robustness ----------------------------------------


def test_store_roundtrip(tmp_path):
    st = PlanStore(str(tmp_path))
    key = _key()
    rec = PlanRecord(
        tier="windowed", block_rows=256, block_cols=512, ring=True,
        pipeline=False, dispatch="blocked", cost_s=1.25,
        source="probe", probe_dim=2048,
    )
    st.put(key, rec)
    # a SECOND process (fresh instance, same dir) sees the plan
    st2 = PlanStore(str(tmp_path))
    got = st2.lookup(key)
    assert got == rec
    assert st2.entries() == 1
    assert st2.stats()["hits"] == 1 and st2.stats()["invalid_lines"] == 0


def test_store_append_only_later_line_wins(tmp_path):
    st = PlanStore(str(tmp_path))
    key = _key()
    st.put(key, PlanRecord(tier="scan", cost_s=9.0))
    st.put(key, PlanRecord(tier="windowed", cost_s=1.0))
    st2 = PlanStore(str(tmp_path))
    assert st2.lookup(key).tier == "windowed"
    assert st2.entries() == 1  # one key, latest record
    with open(st2.file) as f:
        assert len(f.readlines()) == 2  # append-only log


def test_store_schema_mismatch_ignored(tmp_path):
    st = PlanStore(str(tmp_path))
    key = _key()
    st.put(key, PlanRecord(tier="windowed", cost_s=1.0))
    with open(st.file, "a") as f:
        f.write(json.dumps({
            "v": "combblas_tpu.plans/v999",
            "key": key.to_json(),
            "plan": {"tier": "scan"},
        }) + "\n")
    st2 = PlanStore(str(tmp_path))
    # the future-schema line is skipped, never guessed at
    assert st2.lookup(key).tier == "windowed"
    assert st2.stats()["invalid_lines"] == 1


def test_store_corrupted_and_truncated_lines_ignored(tmp_path):
    st = PlanStore(str(tmp_path))
    key = _key()
    st.put(key, PlanRecord(tier="scan", cost_s=2.0))
    good_line = json.dumps({
        "v": SCHEMA, "key": _key(sr="min_plus").to_json(),
        "plan": PlanRecord(tier="windowed", cost_s=1.0).to_json(),
    })
    with open(st.file, "a") as f:
        f.write("not json at all\n")
        f.write(good_line + "\n")
        f.write(json.dumps({"v": SCHEMA, "key": {"op": "spgemm"}}) + "\n")
        f.write(json.dumps({
            "v": SCHEMA, "key": key.to_json(),
            "plan": {"tier": "warp_drive"},  # unknown tier
        }) + "\n")
        f.write(good_line[: len(good_line) // 2])  # torn final write
    st2 = PlanStore(str(tmp_path))
    assert st2.entries() == 2  # the two valid records survive
    assert st2.lookup(key).tier == "scan"
    assert st2.lookup(_key(sr="min_plus")).tier == "windowed"
    assert st2.stats()["invalid_lines"] == 4


def test_store_damaged_file_still_routes(tmp_path, monkeypatch, rng):
    """A plans file of pure garbage must leave spgemm_auto on the
    heuristic path — the robustness contract end to end."""
    (tmp_path / "plans.jsonl").write_text("garbage\n{\n\x00\n")
    st = _use_store(monkeypatch, tmp_path)
    assert st.entries() == 0 and st.stats()["invalid_lines"] >= 2
    grid = Grid.make(1, 1)
    r, c, v = coo(rng, 64, 64, 300)
    A = SpParMat.from_global_coo(grid, r, c, v, 64, 64)
    C = spgemm_auto(PLUS_TIMES, A, A)  # heuristic fallback, no raise
    np.testing.assert_allclose(
        dense_of(C), dense_of(spgemm(PLUS_TIMES, A, A)),
        rtol=1e-5, atol=1e-6,
    )
    # an all-garbage store loads EMPTY, so the router skips the keyed
    # lookup entirely (no D2H spent on a store that can't hit)
    assert st.stats()["misses"] == 0 and st.stats()["hits"] == 0


def test_store_disabled_by_env(monkeypatch):
    monkeypatch.setenv(config.ENV_PLAN_STORE, "0")
    tstore._reset_for_tests()
    assert config.store_dir() is None
    assert tstore.get_store() is None


def test_store_default_is_compile_cache_sibling(monkeypatch):
    monkeypatch.delenv(config.ENV_PLAN_STORE, raising=False)
    from combblas_tpu.utils import compile_cache

    d = config.store_dir()
    assert os.path.basename(d) == ".plan_store"
    assert os.path.dirname(d) == os.path.dirname(
        os.path.abspath(compile_cache.CACHE_DIR)
    )


def test_key_buckets_and_bands():
    assert shape_bucket(1 << 14) == 14
    assert shape_bucket((1 << 14) + 1) == 15  # ceil, not floor
    assert density_band(16 * 1024, 1024) == 4  # avg degree 16
    assert density_band(0, 1024) == -8  # clamped floor
    # the host-count key and the matrix key agree
    grid = Grid.make(1, 1)
    n, nnz = 256, 2048
    rng = np.random.default_rng(7)
    r = rng.integers(0, n, nnz).astype(np.int64)
    c = rng.integers(0, n, nnz).astype(np.int64)
    key = np.unique(r * n + c)
    A = SpParMat.from_global_coo(
        grid, key // n, key % n, np.ones(len(key), np.float32), n, n
    )
    k_mat = spgemm_plan_key(PLUS_TIMES, A, A, "scatter")
    k_cnt = plan_key_from_counts(
        "plus_times", n, n, n, len(key), len(key), "scatter", "1x1"
    )
    assert k_mat == k_cnt


# --- probe -----------------------------------------------------------------


def test_downsample_deterministic_and_band_preserving():
    rng = np.random.default_rng(3)
    n, nnz, p = 5000, 40000, 1024
    r = rng.integers(0, n, nnz)
    c = rng.integers(0, n, nnz)
    a1 = downsample_coo(r, c, (n, n), (p, p), seed=11)
    a2 = downsample_coo(r, c, (n, n), (p, p), seed=11)
    for x, y in zip(a1, a2):
        np.testing.assert_array_equal(x, y)
    assert len(a1[0]) > 0
    assert a1[0].max() < p and a1[1].max() < p
    # restrict-one/fold-one keeps the AVERAGE DEGREE of the original
    # (restricting both axes would shrink it by p/n and measure the
    # rungs in the wrong density band)
    deg_orig = nnz / n
    deg_proxy = len(a1[0]) / p
    assert abs(deg_proxy - deg_orig) / deg_orig < 0.15, (
        deg_proxy, deg_orig
    )
    assert density_band(len(a1[0]), p) == density_band(nnz, n)
    # the B-side split preserves degree the same way
    b = downsample_coo(r, c, (n, n), (p, p), seed=11,
                       modes=("fold", "restrict"))
    assert abs(len(b[0]) / p - deg_orig) / deg_orig < 0.15


def test_probe_deterministic_winner_and_persistence(tmp_path, rng):
    grid = Grid.make(1, 1)
    r, c, v = coo(rng, 128, 128, 800)
    A = SpParMat.from_global_coo(grid, r, c, v, 128, 128)
    key = spgemm_plan_key(PLUS_TIMES, A, A, "scatter")

    def run_once(subdir):
        st = PlanStore(str(tmp_path / subdir))
        seq = iter([0.3, 0.01, 0.2, 0.5])  # injected deterministic costs

        rec = probe_spgemm(
            PLUS_TIMES, A, A, backend="scatter", store=st, key=key,
            measure=lambda fn: next(seq),
            geometry=False,  # tier determinism under test, not the sweep
        )
        return st, rec

    st1, rec1 = run_once("a")
    st2, rec2 = run_once("b")
    # same inputs + same injected costs => identical plan, both runs
    assert rec1 == rec2
    assert rec1.source == "probe" and rec1.cost_s == 0.01
    assert rec1.probe_dim == 128
    # persisted: a fresh load routes from the measured record
    assert PlanStore(st1.path).lookup(key) == rec1
    assert st1.stats()["probe_runs"] >= 2


def test_probe_budget_caps_candidates(tmp_path, rng):
    grid = Grid.make(1, 1)
    r, c, v = coo(rng, 64, 64, 300)
    A = SpParMat.from_global_coo(grid, r, c, v, 64, 64)
    st = PlanStore(str(tmp_path))
    rec = probe_spgemm(
        PLUS_TIMES, A, A, backend="scatter", store=st,
        key=spgemm_plan_key(PLUS_TIMES, A, A, "scatter"),
        budget_s=0.0,  # exhausted after the FIRST (heuristic) rung
        measure=lambda fn: 5.0,
    )
    assert rec is not None  # the first rung is always measured
    assert st.stats()["probe_runs"] == 1


def test_probe_real_measure_smoke(tmp_path, rng):
    """One real (wall-clock) probe on a tiny product: returns a sane
    record and persists it."""
    grid = Grid.make(1, 1)
    r, c, v = coo(rng, 96, 96, 500)
    A = SpParMat.from_global_coo(grid, r, c, v, 96, 96)
    st = PlanStore(str(tmp_path))
    key = spgemm_plan_key(PLUS_TIMES, A, A, "scatter")
    rec = probe_spgemm(
        PLUS_TIMES, A, A, backend="scatter", store=st, key=key,
        geometry=False,  # wall-clock tier smoke; the sweep has its own tests
    )
    assert rec is not None and rec.tier in ("mxu", "windowed", "scan")
    assert rec.cost_s > 0
    assert st.lookup(key) == rec
    assert st.stats()["probe_seconds"] > 0


def test_store_invalid_dispatch_line_ignored(tmp_path):
    st = PlanStore(str(tmp_path))
    key = _key()
    with open(os.path.join(str(tmp_path), "plans.jsonl"), "a") as f:
        f.write(json.dumps({
            "v": SCHEMA, "key": key.to_json(),
            "plan": {"tier": "windowed", "dispatch": "block"},
        }) + "\n")
    st2 = PlanStore(str(tmp_path))
    # a schema-valid but unknown-dispatch line is invalid, not asserted
    # on later at routing time
    assert st2.lookup(key) is None
    assert st2.stats()["invalid_lines"] == 1


def test_store_wrong_op_tier_record_falls_back(
    tmp_path, monkeypatch, rng
):
    """An SpMM tier under a spgemm key (hand-mangled store) is
    rejected at routing — heuristic fallback, no assert."""
    grid = Grid.make(1, 1)
    r, c, v = coo(rng, 64, 64, 300)
    A = SpParMat.from_global_coo(grid, r, c, v, 64, 64)
    st = _use_store(monkeypatch, tmp_path)
    key = spgemm_plan_key(PLUS_TIMES, A, A, "scatter")
    st._plans[key] = PlanRecord(tier="mxu_gather")  # bypass put()'s surface
    C = spgemm_auto(PLUS_TIMES, A, A)
    np.testing.assert_allclose(
        dense_of(C), dense_of(spgemm(PLUS_TIMES, A, A)),
        rtol=1e-5, atol=1e-6,
    )


def test_proxy_dim_never_exceeds_cap():
    from combblas_tpu.tuner.probe import _proxy_dim

    assert _proxy_dim(1 << 14, 2048) == 2048
    assert _proxy_dim(1 << 14, 3000) == 2048  # non-pow2 cap: round DOWN
    assert _proxy_dim(128, 2048) == 128
    assert _proxy_dim(100, 2048) == 128  # small dims still pow2-ceil


def test_ring_wins_over_explicit_blocked(rng):
    """ring is a fused-only schedule: an explicit dispatch='blocked'
    yields to it (obs-counted), instead of silently dropping the
    carousel request."""
    grid = Grid.make(2, 2)
    m = 64
    r, c, v = coo(rng, m, m, 400)
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


# --- store-routed vs heuristic-routed agreement ----------------------------


@pytest.mark.parametrize("srname", [
    "plus_times",
    # store ROUTING is semiring-independent code; the tropical
    # semirings re-pay the Pallas-kernel compiles purely to re-prove
    # it (round 17 budget) — their bit-exactness lives in the spgemm
    # suites, plus_times keeps both grid sizes as the representative
    pytest.param("min_plus", marks=pytest.mark.slow),
    pytest.param("max_min", marks=pytest.mark.slow),
])
@pytest.mark.parametrize("p", [1, 2])
def test_store_routed_bit_exact_vs_heuristic(
    tmp_path, monkeypatch, rng, srname, p
):
    """spgemm_auto routed by a remembered plan must agree with the
    heuristic-routed product on 1x1 AND 2x2 grids across semirings
    with duplicate-entry COO (the store only picks among exact
    kernels)."""
    sr = SRS[srname]
    grid = Grid.make(p, p)
    m = 64
    r, c, v = coo(rng, m, m, 500, dup_frac=0.2)
    A = SpParMat.from_global_coo(grid, r, c, v, m, m)
    # heuristic route (store disabled)
    monkeypatch.setenv(config.ENV_PLAN_STORE, "0")
    tstore._reset_for_tests()
    C_heur = spgemm_auto(sr, A, A)
    # store route: a remembered windowed plan for this key
    st = _use_store(monkeypatch, tmp_path)
    key = spgemm_plan_key(sr, A, A, "scatter")
    st.put(key, PlanRecord(
        tier="windowed", block_rows=16, cost_s=0.5, source="probe",
    ))
    C_store = spgemm_auto(sr, A, A)
    assert st.stats()["hits"] == 1
    np.testing.assert_allclose(
        dense_of(C_store), dense_of(C_heur), rtol=1e-5, atol=1e-6
    )


def test_precedence_arg_over_store_over_env(tmp_path, monkeypatch, rng):
    """The documented chain (tuner/config.py): arg > store > env >
    heuristic."""
    grid = Grid.make(1, 1)
    r, c, v = coo(rng, 64, 64, 300, dup_frac=0.0)
    A = SpParMat.from_global_coo(grid, r, c, v, 64, 64)
    st = _use_store(monkeypatch, tmp_path)
    key = spgemm_plan_key(PLUS_TIMES, A, A, "scatter")
    st.put(key, PlanRecord(tier="scan", cost_s=0.5))
    monkeypatch.setenv(config.ENV_TIER, "windowed")
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        # store beats env
        spgemm_auto(PLUS_TIMES, A, A)
        assert obs.registry.get_counter(
            "spgemm.auto.plan_source", source="store", tier="scan",
            op="spgemm",
        ) == 1
        # arg beats store
        obs.reset()
        spgemm_auto(PLUS_TIMES, A, A, tier="esc")
        assert obs.registry.get_counter(
            "spgemm.auto.plan_source", source="arg", tier="esc",
            op="spgemm",
        ) == 1
        # env beats heuristic (store miss: different semiring key)
        obs.reset()
        spgemm_auto(MIN_PLUS, A, A)
        assert obs.registry.get_counter(
            "spgemm.auto.plan_source", source="env", tier="windowed",
            op="spgemm",
        ) == 1
        # heuristic when nothing else decides
        monkeypatch.delenv(config.ENV_TIER)
        obs.reset()
        spgemm_auto(MAX_MIN, A, A)
        snap = {
            (m_["name"], m_["labels"].get("source"))
            for m_ in obs.registry.snapshot()
            if m_["name"] == "spgemm.auto.plan_source"
        }
        assert snap == {("spgemm.auto.plan_source", "heuristic")}
    finally:
        obs.disable()
        obs.reset()


def test_explicit_schedule_args_beat_store_record(
    tmp_path, monkeypatch, rng
):
    """arg > store holds for the schedule flags too: an explicit
    ring=False must override a remembered ring=True plan (tri-state
    defaults in spgemm_auto)."""
    grid = Grid.make(2, 2)
    m = 64
    r, c, v = coo(rng, m, m, 400, dup_frac=0.0)
    A = SpParMat.from_global_coo(grid, r, c, v, m, m)
    st = _use_store(monkeypatch, tmp_path)
    st.put(
        spgemm_plan_key(PLUS_TIMES, A, A, "scatter"),
        PlanRecord(tier="windowed", block_rows=16, ring=True),
    )
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        spgemm_auto(PLUS_TIMES, A, A, ring=False)  # explicit override
        assert obs.registry.get_counter(
            "spgemm.windowed.dispatch", mode="blocked"
        ) == 1  # ring=False => the blocked building-block default
        obs.reset()
        spgemm_auto(PLUS_TIMES, A, A)  # default: record's ring wins
        assert obs.registry.get_counter(
            "spgemm.windowed.dispatch", mode="fused"
        ) == 1  # ring carousel is fused-only
    finally:
        obs.disable()
        obs.reset()


def test_store_mxu_plan_respects_dedup_guard(tmp_path, monkeypatch, rng):
    """A remembered mxu plan must NOT bypass the unique-entries
    precondition: duplicate-entry inputs fall back (and stay exact)."""
    grid = Grid.make(1, 1)
    m = 64
    r, c, v = coo(rng, m, m, 400, dup_frac=0.25)
    A = SpParMat.from_global_coo(grid, r, c, v, m, m)
    st = _use_store(monkeypatch, tmp_path)
    st.put(
        spgemm_plan_key(PLUS_TIMES, A, A, "scatter"),
        PlanRecord(tier="mxu", cost_s=0.1),
    )
    C = spgemm_auto(PLUS_TIMES, A, A)
    np.testing.assert_allclose(
        dense_of(C), dense_of(spgemm(PLUS_TIMES, A, A)),
        rtol=1e-5, atol=1e-6,
    )


# --- building-block dispatch / bucketed caps -------------------------------


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
    r, c, v = coo(rng, m, m, 800)
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


# --- serve warmup widths -----------------------------------------------------


def test_warmup_explicit_widths_unchanged(tmp_path, monkeypatch):
    from combblas_tpu.serve.engine import GraphEngine

    _use_store(monkeypatch, tmp_path)
    rng = np.random.default_rng(6)
    N = 32
    rows = rng.integers(0, N, 100).astype(np.int64)
    cols = rng.integers(0, N, 100).astype(np.int64)
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), np.concatenate([rows, cols]),
        np.concatenate([cols, rows]), N, kinds=("bfs",),
    )
    warmed = eng.warmup(widths=(2, 4))
    assert set(warmed) == {("bfs", 2), ("bfs", 4)}


def test_warmup_default_widths_and_store_untouched(tmp_path, monkeypatch):
    """One place decides which lanes are warmed: no ``widths`` means
    ``DEFAULT_WARMUP_WIDTHS`` and nothing else, and neither a warm-up
    nor a plan-cache miss writes to the plan store."""
    from combblas_tpu.serve.engine import GraphEngine

    st = _use_store(monkeypatch, tmp_path)
    rng = np.random.default_rng(7)
    N = 32
    rows = rng.integers(0, N, 100).astype(np.int64)
    cols = rng.integers(0, N, 100).astype(np.int64)
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), np.concatenate([rows, cols]),
        np.concatenate([cols, rows]), N, kinds=("bfs",),
    )
    eng.plan("bfs", 32)  # a miss outside the default widths
    warmed = eng.warmup()
    assert set(warmed) == {
        ("bfs", w) for w in GraphEngine.DEFAULT_WARMUP_WIDTHS
    }
    assert st.entries() == 0
    assert not os.path.exists(st.file) or os.path.getsize(st.file) == 0


# --- shared health surface -------------------------------------------------


def test_compile_cache_provider_covers_plan_store(tmp_path, monkeypatch):
    from combblas_tpu.utils import compile_cache

    st = _use_store(monkeypatch, tmp_path)
    st.put(_key(), PlanRecord(tier="windowed", cost_s=1.0))
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        compile_cache._record_cache_entries()
        assert obs.registry.get_gauge(
            "tuner.store.entries", dir=st.path
        ) == 1
        assert obs.registry.get_gauge(
            "compile_cache.entries", cache="plans", dir=st.path
        ) == 1
    finally:
        obs.disable()
        obs.reset()


# --- round 11: store aging (compaction + oldest-cost eviction) --------------


def _key_i(i: int) -> PlanKey:
    """Distinct keys (different shape buckets) for aging tests."""
    return plan_key_from_counts(
        "plus_times", 1 << (8 + i), 1 << (8 + i), 1 << (8 + i),
        1 << (10 + i), 1 << (10 + i), "scatter", "1x1",
        platform="cpu",
    )


def test_store_ts_stamped_and_roundtrips(tmp_path):
    st = PlanStore(str(tmp_path))
    rec = PlanRecord(tier="scan", cost_s=1.0)
    assert rec.ts is None
    st.put(_key(), rec)
    assert rec.ts is not None  # put stamps the measurement time
    got = PlanStore(str(tmp_path)).lookup(_key())
    assert got.ts == rec.ts


def test_store_compaction_rewrites_superseded_lines(
    tmp_path, monkeypatch
):
    """Load-time compaction: a log full of last-wins-shadowed lines is
    rewritten to one line per surviving key (atomic replace), counted
    in stats and the ``tuner.store.compacted`` counter."""
    monkeypatch.setenv(config.ENV_STORE_COMPACT, "5")
    st = PlanStore(str(tmp_path))
    for i in range(8):  # 7 superseded lines for one key
        st.put(_key(), PlanRecord(tier="scan", cost_s=float(i + 1)))
    st.put(_key_i(1), PlanRecord(tier="windowed", cost_s=0.5))
    with open(st.file) as f:
        assert len(f.readlines()) == 9
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        st2 = PlanStore(str(tmp_path))
        assert st2.entries() == 2
        assert st2.stats()["compacted_lines"] == 7
        assert obs.registry.get_counter("tuner.store.compacted") == 7
        with open(st2.file) as f:
            lines = f.readlines()
        assert len(lines) == 2  # the rewritten file is compact
        # survivors keep their latest records
        assert st2.lookup(_key()).cost_s == 8.0
        assert st2.lookup(_key_i(1)).tier == "windowed"
        # a third load has nothing to compact
        st3 = PlanStore(str(tmp_path))
        assert st3.stats()["compacted_lines"] == 0
    finally:
        obs.disable()
        obs.reset()


def test_store_compaction_below_threshold_keeps_log(tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv(config.ENV_STORE_COMPACT, "50")
    st = PlanStore(str(tmp_path))
    for i in range(4):
        st.put(_key(), PlanRecord(tier="scan", cost_s=float(i + 1)))
    st2 = PlanStore(str(tmp_path))
    assert st2.stats()["compacted_lines"] == 0
    with open(st2.file) as f:
        assert len(f.readlines()) == 4  # append-only log untouched


def test_store_max_entries_oldest_cost_eviction(tmp_path, monkeypatch):
    """The cap evicts by measurement age: oldest ``ts`` first (records
    without one age out before any stamped record), newest survive —
    at load AND at put."""
    monkeypatch.setenv(config.ENV_STORE_MAX, "3")
    monkeypatch.setenv(config.ENV_STORE_COMPACT, "1")
    st = PlanStore(str(tmp_path))
    for i in range(5):
        st.put(
            _key_i(i),
            PlanRecord(tier="scan", cost_s=1.0, ts=float(100 + i)),
        )
        assert st.entries() <= 3  # put-time cap holds throughout
    assert st.stats()["evicted"] == 2
    assert st.lookup(_key_i(0)) is None  # oldest ts evicted
    assert st.lookup(_key_i(4)) is not None
    # load-time: the file still carries all 5 lines until a reload
    # compacts; the fresh instance loads, evicts to cap, and rewrites
    st2 = PlanStore(str(tmp_path))
    assert st2.entries() == 3
    assert st2.lookup(_key_i(4)) is not None
    with open(st2.file) as f:
        assert len(f.readlines()) == 3


def test_store_unstamped_records_age_out_first(tmp_path, monkeypatch):
    monkeypatch.setenv(config.ENV_STORE_MAX, "2")
    st = PlanStore(str(tmp_path))
    st.put(_key_i(0), PlanRecord(tier="scan", ts=50.0))
    unstamped = PlanRecord(tier="scan")
    unstamped.ts = None  # simulate a pre-round-11 line
    with st._lock:
        st._plans[_key_i(1)] = unstamped
    st.put(_key_i(2), PlanRecord(tier="scan", ts=60.0))
    assert st.lookup(_key_i(1)) is None  # no ts = oldest
    assert st.lookup(_key_i(0)) is not None


# --- round 11: the shared resolve_tier helper -------------------------------


def test_resolve_tier_precedence_and_vetting(tmp_path, monkeypatch):
    """arg > store > env > heuristic, with the library's record
    vetting: a key-matched record outside ``allowed`` is discarded
    (``tuner.store.rejected{reason=tier}``) and resolution degrades."""
    from combblas_tpu.tuner.resolve import resolve_tier

    st = _use_store(monkeypatch, tmp_path)
    key = _key()
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        # heuristic rung (empty store, no env)
        tier, src, rec = resolve_tier(
            key, op="spgemm", allowed=("scan", "esc"),
            heuristic=lambda: "esc", store=st,
        )
        assert (tier, src, rec) == ("esc", "heuristic", None)
        # store rung
        st.put(key, PlanRecord(tier="scan", cost_s=0.5))
        tier, src, rec = resolve_tier(
            key, op="spgemm", allowed=("scan", "esc"),
            heuristic="esc", store=st,
        )
        assert (tier, src) == ("scan", "store") and rec.tier == "scan"
        # vetting: same record under an op that doesn't allow the tier
        tier, src, rec = resolve_tier(
            key, op="spgemm3d", allowed=("esc", "windowed"),
            heuristic="esc", store=st,
        )
        assert (tier, src, rec) == ("esc", "heuristic", None)
        assert obs.registry.get_counter(
            "tuner.store.rejected", reason="tier"
        ) == 1
        # env rung beats the heuristic when the record was rejected
        monkeypatch.setenv(config.ENV_TIER3D, "windowed")
        tier, src, _rec = resolve_tier(
            key, op="spgemm3d", allowed=("esc", "windowed"),
            heuristic="esc", store=st,
        )
        assert (tier, src) == ("windowed", "env")
        # arg wins over everything
        tier, src, _rec = resolve_tier(
            key, op="spgemm", allowed=("scan", "esc"),
            heuristic="esc", tier="mxu", store=st,
        )
        assert (tier, src) == ("mxu", "arg")
        assert obs.registry.get_counter(
            "spgemm.auto.plan_source", source="arg", tier="mxu",
            op="spgemm",
        ) == 1
    finally:
        obs.disable()
        obs.reset()


def test_resolve_tier_account_false_peeks_silently(tmp_path,
                                                   monkeypatch):
    """account=False (the mirror mode): peek — no hit/miss
    accounting, no plan_source counter."""
    from combblas_tpu.tuner.resolve import resolve_tier

    st = _use_store(monkeypatch, tmp_path)
    key = _key(op="spgemm3d")
    st.put(key, PlanRecord(tier="windowed", cost_s=0.5))
    hits_before = st.stats()["hits"]
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        tier, src, _rec = resolve_tier(
            key, op="spgemm3d", allowed=("esc", "windowed"),
            heuristic="esc", store=st, account=False,
        )
        assert (tier, src) == ("windowed", "store")
        assert st.stats()["hits"] == hits_before  # peek, not lookup
        assert obs.registry.get_counter(
            "spgemm.auto.plan_source", source="store",
            tier="windowed", op="spgemm3d",
        ) == 0
    finally:
        obs.disable()
        obs.reset()


# --- round 12: window-geometry probing --------------------------------------


def test_probe_geometry_sweep_records_block_shape(tmp_path, rng):
    """When the tier sweep's winner is ``windowed`` and budget remains,
    the probe sweeps a bounded block-geometry grid and persists the
    winning block_rows/block_cols WITH the plan."""
    from combblas_tpu.tuner.probe import _geometry_candidates

    grid = Grid.make(1, 1)
    r, c, v = coo(rng, 128, 128, 700, dup_frac=0.0)
    A = SpParMat.from_global_coo(grid, r, c, v, 128, 128)
    st = PlanStore(str(tmp_path))
    key = spgemm_plan_key(PLUS_TIMES, A, A, "scatter")
    geo = _geometry_candidates(128, 128)
    assert 1 <= len(geo) <= 5 and (None, None) not in geo
    # injected costs: make "windowed" win the tier sweep (0.4 beats
    # scan's 0.5), then make the SECOND geometry candidate the overall
    # winner (0.05)
    seq = iter([0.4, 0.5] + [0.9, 0.05] + [0.7] * 8)

    rec = probe_spgemm(
        PLUS_TIMES, A, A, backend="scatter", store=st, key=key,
        tier_order=("windowed", "scan"),
        measure=lambda fn: next(seq),
    )
    assert rec is not None and rec.tier == "windowed"
    assert (rec.block_rows, rec.block_cols) == geo[1]
    assert rec.cost_s == 0.05
    # persisted: a fresh load replays the measured geometry
    assert PlanStore(str(tmp_path)).lookup(key) == rec


def test_probe_geometry_skipped_when_windowed_loses(tmp_path, rng):
    grid = Grid.make(1, 1)
    r, c, v = coo(rng, 64, 64, 300, dup_frac=0.0)
    A = SpParMat.from_global_coo(grid, r, c, v, 64, 64)
    st = PlanStore(str(tmp_path))
    seq = iter([0.1, 0.5, 0.5, 0.5])

    rec = probe_spgemm(
        PLUS_TIMES, A, A, backend="scatter", store=st,
        key=spgemm_plan_key(PLUS_TIMES, A, A, "scatter"),
        tier_order=("scan", "windowed"),
        measure=lambda fn: next(seq),
    )
    assert rec is not None and rec.tier == "scan"
    assert rec.block_rows is None and rec.block_cols is None
