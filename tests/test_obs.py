"""Telemetry subsystem (combblas_tpu/obs): registry, spans, JSONL
round-trip, multihost merge and zero-cost-when-disabled
(docs/observability.md)."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu import obs
from combblas_tpu.models.bfs import clear_bfs_caches
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.semiring import SELECT2ND_MAX

from conftest import random_dense


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _graph(rng, n=48, density=0.12, grid_shape=(2, 2)):
    grid = Grid.make(*grid_shape)
    d = (rng.random((n, n)) < density).astype(np.float32)
    d = np.maximum(d, d.T)
    np.fill_diagonal(d, 0.0)
    return SpParMat.from_dense(grid, d), d


# --- registry ---------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    obs.enable(install_hooks=False)
    obs.count("c", 2)
    obs.count("c", 3)
    obs.count("c", 1, kernel="x")  # distinct labeled series
    obs.gauge("g", 1.5, op="summa")
    obs.observe("h", 0.1)
    obs.observe("h", 0.3)
    r = obs.registry
    assert r.get_counter("c") == 5
    assert r.get_counter("c", kernel="x") == 1
    assert r.get_gauge("g", op="summa") == 1.5
    h = r.get_histogram("h")
    assert h["count"] == 2 and abs(h["sum"] - 0.4) < 1e-9
    assert h["min"] == 0.1 and h["max"] == 0.3
    kinds = {rec["kind"] for rec in r.snapshot()}
    assert kinds == {"counter", "gauge", "histogram"}


def test_span_nesting_events_and_table():
    obs.enable(install_hooks=False)
    with obs.span("outer", scale=4):
        obs.span_event("tick", i=0)
        with obs.span("inner"):
            time.sleep(0.001)
    table = obs.report()
    assert set(table) >= {"outer", "inner"}
    assert table["outer"][0] >= table["inner"][0] > 0
    inner = [s for s in obs._spans.log if s["name"] == "inner"][0]
    assert inner["path"] == "outer/inner"
    outer = [s for s in obs._spans.log if s["name"] == "outer"][0]
    assert outer["attrs"] == {"scale": 4}
    assert outer["events"][0]["name"] == "tick"


def test_forced_span_still_accumulates_when_obs_disabled():
    obs.reset_spans()
    assert not obs.ENABLED
    with obs.span("forced_phase", force=True):
        pass
    assert "forced_phase" in obs.report()
    assert obs.span_seconds("forced_phase") >= 0
    # but the metrics registry stays untouched
    assert obs.registry.empty()


# --- zero-cost-when-disabled ------------------------------------------------


def _served_bfs(rng):
    """A small served BFS engine, its width-16 plan warm, and a batch."""
    from combblas_tpu.serve import GraphEngine

    n = 64
    r = rng.integers(0, n, 400)
    c = rng.integers(0, n, 400)
    engine = GraphEngine.from_coo(
        Grid.make(2, 2), np.concatenate([r, c]), np.concatenate([c, r]),
        n, kinds=("bfs",),
    )
    engine.warmup(kinds=("bfs",), widths=(16,))
    return engine, np.arange(16, dtype=np.int32)


def _bare(engine, sources):
    """What ``engine.execute`` wraps: the plan's call and the readback of
    its result blocks and iteration count."""
    p, l, niter, *_ = engine.plan("bfs", 16).fn(jnp.asarray(sources))
    return (engine._lanes_to_global(np.asarray(p)),
            engine._lanes_to_global(np.asarray(l)), int(niter))


def test_disabled_instrumentation_is_free(rng, monkeypatch):
    """The served batch path with telemetry off, held to the contract and
    not to a clock: it enters no method of ``obs.registry``,
    ``obs._spans`` or ``obs.trace`` (every one raises here), and it reads
    back no output of the plan beyond its result blocks: the sweep tally
    and the pushes' report (level 0's outcome, the levels walked and
    their edges, PR 52) stay on the device, as many ``np.asarray`` /
    ``device_get`` calls as the bare plan call and readback it wraps."""
    import types

    from combblas_tpu.serve import engine as engine_mod

    engine, sources = _served_bfs(rng)
    plan = engine.plan("bfs", 16)
    assert not obs.ENABLED

    def refuse(what):
        def entered(*a, **kw):
            raise AssertionError(f"telemetry off, and {what} was entered")
        return entered

    outputs, read = [], []
    fn = plan.fn

    def counting_asarray(x, *a, **kw):
        if isinstance(x, jax.Array):
            read.append(x)
        return np.asarray(x, *a, **kw)

    def recording_fn(srcs):
        outputs.append(fn(srcs))
        return outputs[-1]

    with monkeypatch.context() as m:
        for holder in (obs.registry, obs._spans, obs.trace):
            for name in dir(holder):
                attr = getattr(holder, name)
                if not name.startswith("__") and isinstance(
                        attr, (types.MethodType, types.FunctionType)):
                    m.setattr(holder, name, refuse(
                        f"{getattr(holder, '__name__', type(holder).__name__)}"
                        f".{name}"))
        m.setattr(jax, "device_get", refuse("jax.device_get"))
        # the engine's own numpy: everything but ``asarray`` as it is
        m.setattr(engine_mod, "np", types.SimpleNamespace(
            **{**vars(np), "asarray": counting_asarray}))
        m.setattr(plan, "fn", recording_fn)
        out = engine.execute("bfs", sources)
    # parents and levels, in the program's order, and nothing after them
    # (the iteration count is one ``int()``; the tally and the pushes'
    # report, outputs 3 and 4, were left where they are)
    (res,) = outputs
    assert len(res) == 5 and [id(x) for x in read] == [
        id(res[0]), id(res[1])]
    bare = _bare(engine, sources)
    np.testing.assert_array_equal(bare[0], out["parents"])
    np.testing.assert_array_equal(bare[1], out["levels"])
    assert bare[2] == out["batch_niter"]
    assert set(out) == {"parents", "levels", "batch_niter"}
    assert obs.registry.empty() and obs._spans.empty()
    assert not obs.trace.records()


@pytest.mark.slow
def test_disabled_instrumentation_costs_no_time(rng):
    """The same path against the same bare call on the clock: under 5%
    of wall time between them (interleaved, min-filtered: a load spike
    cannot land on one side only).  Two wall clocks under ``-n 6`` decide
    nothing (ROADMAP, PR 35), so this runs with the slow tests; tier-1
    holds the contract itself (the test above)."""
    engine, sources = _served_bfs(rng)

    def sample(fn):
        t0 = time.perf_counter()
        for _ in range(5):
            fn()
        return time.perf_counter() - t0

    bare_t, served_t = [], []
    for _ in range(9):
        bare_t.append(sample(lambda: _bare(engine, sources)))
        served_t.append(sample(lambda: engine.execute("bfs", sources)))
    assert min(served_t) <= min(bare_t) * 1.05 + 0.005, (
        min(served_t), min(bare_t))
    assert obs.registry.empty() and obs._spans.empty()


def test_windowed_dot_counters_gated(rng):
    """ISSUE 5 satellite: a forced windowed-dot SpGEMM emits the
    ``spgemm.auto.tier{tier=windowed}`` counter and the 2D skip
    counters under obs — and NOTHING when disabled (the zero-cost gate
    extended to the round-7 counter series)."""
    from combblas_tpu import PLUS_TIMES
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spgemm import spgemm_auto
    from combblas_tpu.parallel.spmat import SpParMat

    grid = Grid.make(1, 1)
    m = 64
    r = rng.integers(0, m, 300).astype(np.int64)
    c = rng.integers(0, m, 300).astype(np.int64)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(300, np.float32), m, m
    )
    assert not obs.ENABLED
    spgemm_auto(
        PLUS_TIMES, A, A, tier="windowed", backend="dot",
        block_rows=32, block_cols=32,
    )
    assert obs.registry.empty()  # disabled: zero bookkeeping
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        spgemm_auto(
            PLUS_TIMES, A, A, tier="windowed", backend="dot",
            block_rows=32, block_cols=32,
        )
        assert obs.registry.get_counter(
            "spgemm.auto.tier", tier="windowed", sr="plus_times"
        ) == 1
        assert obs.registry.get_gauge(
            "spgemm.windowed.col_windows"
        ) == 2
        assert obs.registry.get_counter(
            "spgemm.windowed.col_windows_skipped"
        ) >= 0
        assert obs.registry.get_gauge(
            "spgemm.windowed.panel_cells"
        ) == 512 * 512
    finally:
        obs.disable()
        obs.reset()


@pytest.mark.slow  # round 12 (tier-1 budget): 16 s of r9 kernel
# compiles purely for counter bookkeeping; the zero-cost gate
# MECHANISM stays tier-1 via the round-10/11/12 gate tests
def test_round9_pipeline_pack_3d_counters_gated(rng):
    """ISSUE 7 satellite: the round-9 series — pipelined-carousel
    overlap count, packed-launch counters, and the 3D layers gauge —
    are emitted under obs and cost NOTHING when disabled (the zero-cost
    gate extended to the round-9 series)."""
    from combblas_tpu import PLUS_TIMES
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.mesh3d import Grid3D
    from combblas_tpu.parallel.spgemm import spgemm_auto, spgemm_windowed
    from combblas_tpu.parallel.spmat import SpParMat

    grid = Grid.make(2, 2)
    m = 64
    r = rng.integers(0, m, 400).astype(np.int64)
    c = rng.integers(0, m, 400).astype(np.int64)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(400, np.float32), m, m
    )
    assert not obs.ENABLED
    spgemm_windowed(
        PLUS_TIMES, A, A, block_rows=16, backend="scatter", ring=True
    )
    assert obs.registry.empty()  # disabled: zero bookkeeping
    assert obs._spans.empty()
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        # fresh static config (different block_rows) forces a retrace so
        # the trace-time counters fire under the enabled registry
        spgemm_windowed(
            PLUS_TIMES, A, A, block_rows=8, backend="scatter", ring=True
        )
        assert obs.registry.get_counter(
            "spgemm.pipeline.stages_overlapped"
        ) == grid.pr - 1
        assert obs.registry.get_counter(
            "trace.summa_spgemm_windowed", backend="scatter", ring=True
        ) == 1
        packed = obs.registry.get_counter("spgemm.windowed.windows_packed")
        assert packed >= 1
        ratio = obs.registry.get_gauge("spgemm.windowed.pack_ratio")
        assert 0 < ratio <= 1.0
        # the 3D route records its layer count
        obs.reset()
        g3 = Grid3D.make(2, 2, 2)
        spgemm_auto(
            PLUS_TIMES, A, A, tier="windowed3d", grid3=g3,
            backend="scatter", block_rows=16,
        )
        assert obs.registry.get_gauge("spgemm.summa3d.layers") == 2
        assert obs.registry.get_counter(
            "spgemm.auto.tier", tier="windowed3d", sr="plus_times"
        ) == 1
    finally:
        obs.disable()
        obs.reset()


def test_round13_merge_counters_gated(rng):
    """ISSUE 11 satellite: the round-13 merge-tier series —
    ``spgemm.merge.tier`` and the ``merge``-labeled trace counter —
    are emitted under obs and cost NOTHING when disabled (the
    zero-cost gate extended to the merge tiers).  The heavier 3D
    counters (hash_overflow, piece_overflow, 3D stages_overlapped)
    are asserted by tests/test_spgemm_merge.py on the same
    ``obs.ENABLED``-guarded code paths."""
    from combblas_tpu import PLUS_TIMES
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spgemm import spgemm
    from combblas_tpu.parallel.spmat import SpParMat

    grid = Grid.make(1, 1)
    m = 64
    r = rng.integers(0, m, 300).astype(np.int64)
    c = rng.integers(0, m, 300).astype(np.int64)
    A = SpParMat.from_global_coo(
        grid, r, c, np.ones(300, np.float32), m, m
    )
    assert not obs.ENABLED
    spgemm(PLUS_TIMES, A, A, merge="runs")
    assert obs.registry.empty()  # disabled: zero bookkeeping
    assert obs._spans.empty()
    obs.enable(install_hooks=False)
    try:
        obs.reset()
        spgemm(PLUS_TIMES, A, A, merge="runs")
        assert obs.registry.get_counter(
            "spgemm.merge.tier", tier="runs", source="arg", op="spgemm"
        ) == 1
    finally:
        obs.disable()
        obs.reset()


# --- JSONL round-trip + multihost merge -------------------------------------


def test_jsonl_roundtrip_and_aggregate(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    obs.enable(jsonl_path=path, install_hooks=False)
    with obs.span("phase.a", stage=1):
        obs.span_event("it", round=1, chaos=0.5)
    with obs.span("phase.a", stage=2):
        pass
    obs.count("drops", 3)
    obs.count("drops", 4)
    obs.gauge("imbalance", 2.0, op="spgemm")
    obs.observe("k1.generate_s", 0.25)
    out = obs.dump_jsonl()
    assert out == path
    recs = obs.parse_jsonl(path)  # validates every line against schema
    assert recs[0]["kind"] == "meta" and recs[0]["schema"] == obs.SCHEMA
    agg = obs.aggregate(recs)
    assert agg["counters"]["drops"] == 7
    assert agg["span_table"]["phase.a"][1] == 2
    assert agg["histograms"]["k1.generate_s"]["count"] == 1
    span = [r for r in recs if r["kind"] == "span"][0]
    assert span["events"][0]["chaos"] == 0.5


def test_jsonl_validation_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"v": 1, "kind": "span", "name": "x"}) + "\n")
    with pytest.raises(ValueError):
        obs.parse_jsonl(str(bad))
    worse = tmp_path / "worse.jsonl"
    worse.write_text(json.dumps({"v": 99, "kind": "meta"}) + "\n")
    with pytest.raises(ValueError):
        obs.parse_jsonl(str(worse))


def test_multihost_merge(tmp_path):
    """Per-process JSONL files merged host-side: counters add, spans
    keep their process id (the multi-controller aggregation path)."""
    paths = []
    for proc in (0, 1):
        obs.reset()
        obs.enable(install_hooks=False)
        obs.count("redistribute.dropped", 10 * (proc + 1))
        obs.gauge("hbm.used", 1.0 + proc)
        obs.observe("hop_s", 0.1 * (proc + 1))
        with obs.span("bfs.hop", hop=proc):
            pass
        p = str(tmp_path / f"events.p{proc}.jsonl")
        obs.dump_jsonl(p, process=proc, nprocs=2)
        paths.append(p)
    merged_path = str(tmp_path / "merged.jsonl")
    agg = obs.merge_jsonl_files(paths, merged_path)
    assert agg["counters"]["redistribute.dropped"] == 30
    assert agg["histograms"]["hop_s"]["count"] == 2
    assert agg["span_table"]["bfs.hop"][1] == 2
    assert sorted(s["process"] for s in agg["spans"]) == [0, 1]
    assert {"hbm.used@p0", "hbm.used@p1"} <= set(agg["gauges"])
    # the merged file itself round-trips through the validator
    again = obs.parse_jsonl(merged_path)
    assert again[0]["kind"] == "meta" and again[0]["nprocs"] == 2


@pytest.mark.parametrize("grid_shape", [(2, 4), (1, 1)])
def test_psum_counters_device_aggregation(grid_shape):
    """The in-program add-monoid counter path: per-device counter blocks
    psum'd over the mesh via parallel/collectives (8-device fixture)."""
    grid = Grid.make(*grid_shape)
    pr, pc = grid_shape
    local = np.arange(pr * pc * 3, dtype=np.int32).reshape(pr, pc, 3)
    tot = np.asarray(obs.psum_counters(grid, jnp.asarray(local)))
    np.testing.assert_array_equal(tot, local.sum(axis=(0, 1)))


# --- instrumented hot paths -------------------------------------------------


def test_spgemm_and_redistribute_metrics(rng):
    from combblas_tpu.parallel.spgemm import spgemm
    from combblas_tpu.semiring import PLUS_TIMES

    obs.enable(install_hooks=False, device_sync=True)
    A, d = _graph(rng, n=32)
    C = spgemm(PLUS_TIMES, A, A)
    want = d @ d
    np.testing.assert_allclose(np.asarray(C.to_dense()), want, rtol=1e-5)
    assert obs.registry.get_counter("spgemm.symbolic_fill_slots") > 0
    assert obs.registry.get_counter("spgemm.realized_nnz") == int(
        (want != 0).sum()
    )
    assert obs.registry.get_gauge("spgemm.load_imbalance") >= 1.0
    assert "spgemm" in obs.report()

    # redistribute drop accounting (zero on success, but present)
    from combblas_tpu.parallel.redistribute import from_device_coo

    grid = A.grid
    n = 32
    r, c = np.nonzero(d)
    ndev = grid.pr * grid.pc
    chunk = -(-len(r) // ndev)
    pad = chunk * ndev - len(r)
    r3 = np.concatenate([r.astype(np.int32), np.full(pad, n, np.int32)])
    c3 = np.concatenate([c.astype(np.int32), np.full(pad, n, np.int32)])
    shape = (grid.pr, grid.pc, chunk)
    M = from_device_coo(
        grid,
        jax.device_put(r3.reshape(shape), grid.tile_sharding()),
        jax.device_put(c3.reshape(shape), grid.tile_sharding()),
        jnp.ones(shape, jnp.float32),
        n, n,
    )
    np.testing.assert_array_equal(
        np.asarray(M.to_dense()) != 0, d != 0
    )
    assert obs.registry.get_counter("redistribute.dropped", default=-1) == 0
    assert "redistribute" in obs.report()


def test_bfs_caches_bounded_cleared_and_exported():
    from combblas_tpu.models import bfs as bfs_mod

    clear_bfs_caches()
    assert bfs_mod._gid_blocks.cache_info().currsize == 0
    assert bfs_mod._gid_blocks.cache_info().maxsize == 16
    assert bfs_mod._iota_operand.cache_info().maxsize == 8
    bfs_mod._iota_operand(16)
    bfs_mod._iota_operand(16)
    ci = bfs_mod._iota_operand.cache_info()
    assert ci.currsize == 1 and ci.hits >= 1
    obs.enable(install_hooks=False)
    snap = {
        (r["name"]): r["value"]
        for r in obs.metrics_snapshot()
        if r["kind"] == "gauge"
    }
    assert snap["cache.bfs.iota_operand.size"] == 1
    assert snap["cache.bfs.iota_operand.hits"] >= 1
    assert snap["cache.bfs.gid_blocks.maxsize"] == 16
    clear_bfs_caches()
    assert bfs_mod._iota_operand.cache_info().currsize == 0


def test_round11_dynamic_counters_gated(rng):
    """ISSUE 9 satellite: the round-11 dynamic-mutation series — delta
    depth/ops, merge mode/latency, refresh runs, serve update counters
    — are emitted under obs and cost NOTHING when disabled."""
    from combblas_tpu.dynamic import DeltaBatch, DeltaBuffer, apply_delta
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import GraphEngine, ServeConfig

    n = 48
    r = rng.integers(0, n, 200)
    c = rng.integers(0, n, 200)
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), np.concatenate([r, c]), np.concatenate([c, r]),
        n, kinds=("bfs",), keep_coo=True,
    )
    present = set(
        zip(eng.version.host_coo[0].tolist(),
            eng.version.host_coo[1].tolist())
    )
    a, b = next(
        (a, b) for a in range(n) for b in range(n)
        if a != b and (a, b) not in present
    )
    ops = [("insert", a, b), ("insert", b, a)]

    def exercise():
        buf = DeltaBuffer(capacity=8, nrows=n, ncols=n)
        buf.add_many(ops)
        batch = buf.drain()
        v = apply_delta(eng.version, batch, kinds=eng.kinds())
        eng.refresh("bfs", root=int(r[0]))
        srv = eng.serve(ServeConfig(
            lane_widths=(1,), update_autostart=False,
        ))
        srv.submit_update([("delete", a, b), ("delete", b, a)])
        srv.pump_updates(force=True)
        srv.close()
        return v

    assert not obs.ENABLED
    exercise()
    assert obs.registry.empty()  # disabled: zero bookkeeping

    obs.enable(install_hooks=False)
    try:
        obs.reset()
        eng._analytics.clear()
        exercise()
        g = obs.registry.get_counter
        assert g("dynamic.delta.ops", op="insert") == 2
        assert g("dynamic.delta.batches") >= 1
        assert g("dynamic.merge.applied", mode="incremental") >= 1
        assert obs.registry.get_histogram(
            "dynamic.merge.latency_s"
        )["count"] >= 1
        assert g("dynamic.refresh.runs", kind="bfs", mode="cold") == 1
        assert g("serve.update.submitted") == 1
        assert g("serve.update.merges", mode="incremental") >= 1
        assert obs.registry.get_histogram(
            "serve.update.coalesced"
        )["count"] >= 1
    finally:
        obs.disable()
        obs.reset()


def test_round14_pool_fleet_counters_gated(rng, tmp_path):
    """ISSUE 12 satellite: the round-14 series — pool residency
    gauges/counters, WFQ rounds/served/deficit, fleet routing, and the
    checkpoint histograms — are emitted under obs and cost NOTHING
    when disabled (the zero-cost gate extended to the pool/fleet)."""
    import os

    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import EnginePool, FleetRouter, ServeConfig
    from combblas_tpu.utils import checkpoint

    grid = Grid.make(1, 1)
    n = 32
    r = rng.integers(0, n, 120)
    c = rng.integers(0, n, 120)
    rows = np.concatenate([r, c])
    cols = np.concatenate([c, r])
    cfg = ServeConfig(lane_widths=(1,), update_autostart=False)

    def exercise(tag):
        pool = EnginePool(grid)
        pool.add_tenant(
            "a", rows, cols, n, config=cfg, kinds=("bfs",)
        )
        psrv = pool.serve()
        f = psrv.submit("a", "bfs", 1)
        while psrv.pump(force=True):
            pass
        assert f.exception(timeout=0) is None
        assert pool.evict("a")
        pool.admit("a")  # re-admission: the rebuild path
        path = os.path.join(tmp_path, f"v-{tag}.npz")
        checkpoint.save_version(path, pool.engine("a").version)
        checkpoint.load_version(path, grid)
        fr = FleetRouter([pool.server("a")])
        fr.submit("bfs", 2)
        pool.server("a").scheduler.fail_pending(
            RuntimeError("gate teardown")
        )

    assert not obs.ENABLED
    exercise("off")
    assert obs.registry.empty()  # disabled: zero bookkeeping

    obs.enable(install_hooks=False)
    try:
        obs.reset()
        exercise("on")
        g = obs.registry.get_counter
        assert g("serve.pool.admits", tenant="a") == 2  # build+rebuild
        assert g("serve.pool.evictions", tenant="a") == 1
        assert obs.registry.get_gauge("serve.pool.resident_bytes") > 0
        assert obs.registry.get_gauge("serve.pool.resident_tenants") == 1
        assert obs.registry.get_histogram(
            "serve.pool.rebuild_s"
        )["count"] == 2
        assert g("serve.wfq.rounds") >= 1
        assert g("serve.wfq.served", tenant="a") >= 1
        assert obs.registry.get_gauge(
            "serve.wfq.deficit", tenant="a"
        ) is not None
        assert g("serve.fleet.submitted", replica=0) == 1
        assert obs.registry.get_gauge("serve.fleet.replicas") == 1
        assert obs.registry.get_histogram(
            "serve.checkpoint.save_s"
        )["count"] == 1
        assert obs.registry.get_histogram(
            "serve.checkpoint.load_s"
        )["count"] == 1
        # tenant-labeled scheduler series (end-to-end labels)
        assert obs.registry.get_gauge(
            "serve.queue.depth", tenant="a"
        ) is not None
    finally:
        obs.disable()
        obs.reset()


def test_round16_durability_counters_gated(rng, tmp_path):
    """ISSUE 14 satellite: the round-16 durability & self-healing
    series — WAL appends/truncates, checkpoint reasons, recovery
    replay counters, fleet versions_behind — are emitted under obs and
    cost NOTHING when disabled (one attribute read on every hot
    path)."""
    import os

    from combblas_tpu.dynamic import open_wal, recover_version
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import FleetRouter, GraphEngine, \
        Server, ServeConfig

    grid = Grid.make(1, 1)
    n = 32
    r = rng.integers(0, n, 120)
    c = rng.integers(0, n, 120)
    rows = np.concatenate([r, c])
    cols = np.concatenate([c, r])
    present = set(zip(rows.tolist(), cols.tolist()))
    pairs = [
        (i, j) for i in range(n) for j in range(i + 1, n)
        if (i, j) not in present and (j, i) not in present
    ][:2]

    def exercise(tag):
        d = os.path.join(tmp_path, f"wal-{tag}")
        cfg = ServeConfig(lane_widths=(1,), update_autostart=False,
                          update_flush=1, wal_dir=d,
                          # retain only the newest snapshot so the
                          # manual checkpoint actually truncates
                          # (default retain=2 keeps the bootstrap
                          # snapshot, whose seq pins the WAL suffix)
                          checkpoint_retain=1)
        eng = GraphEngine.from_coo(
            grid, rows, cols, n, kinds=("bfs",), keep_coo=True
        )
        srv = Server(eng, cfg)  # bootstrap checkpoint
        (a, b), (a2, b2) = pairs
        f = srv.submit_update([("insert", a, b), ("insert", b, a)])
        srv.pump_updates(force=True)
        assert f.exception(timeout=0) is None
        srv.checkpoint_now()  # truncates the replayed WAL prefix
        wal = open_wal(d)
        recover_version(d, wal, grid, kinds=("bfs",))
        wal.close()
        srv.scheduler.close()
        # fleet surface: fan-out generation gauges
        fr = FleetRouter.build(
            grid, rows, cols, n, replicas=2, kinds=("bfs",),
            config=ServeConfig(lane_widths=(1,), update_flush=1,
                               update_max_delay_s=0.005),
            start=False,
        )
        fr.replicas[0].submit_update(
            [("insert", a2, b2), ("insert", b2, a2)]
        )
        fr.replicas[0].pump_updates(force=True)
        fr.fan_out()
        fr.close(drain=False)

    assert not obs.ENABLED
    exercise("off")
    assert obs.registry.empty()  # disabled: zero bookkeeping

    obs.enable(install_hooks=False)
    try:
        obs.reset()
        exercise("on")
        g = obs.registry.get_counter
        assert g("serve.wal.appends") == 1  # the acknowledged write
        assert obs.registry.get_histogram(
            "serve.wal.append_s"
        )["count"] == 1
        assert g("serve.wal.truncated") >= 1
        assert g("serve.checkpoint.auto", reason="bootstrap") == 1
        assert g("serve.checkpoint.auto", reason="manual") == 1
        assert g("serve.recovery.runs") == 1
        assert g("serve.recovery.replayed_ops") == 0  # ckpt covered it
        assert obs.registry.get_histogram(
            "serve.recovery.recover_s"
        )["count"] == 1
        assert obs.registry.get_gauge(
            "serve.fleet.versions_behind", replica=1
        ) == 0
        assert g("serve.fleet.fanout") == 1
    finally:
        obs.disable()
        obs.reset()


def test_round17_procfleet_counters_gated():
    """ISSUE 15 satellite: the round-17 process-fleet IPC series —
    per-RPC latency, per-request deadline timeouts, quarantine — are
    emitted under obs and cost NOTHING when disabled.  Exercised
    through the parent-side replica client over an in-process stub
    responder (a socketpair, not a subprocess: the gate measures the
    ROUTER's bookkeeping, and must stay tier-1 cheap)."""
    import socket
    import threading
    import time as _time

    from combblas_tpu.serve.frame import Channel, ChannelClosed
    from combblas_tpu.serve.procfleet import (
        IpcTimeoutError,
        ReplicaDeadError,
        ReplicaProc,
    )

    def exercise(tag):
        a, b = socket.socketpair()
        stop = threading.Event()
        ch_child = Channel(b)

        def responder():
            while not stop.is_set():
                try:
                    m = ch_child.recv(timeout=0.05)
                except socket.timeout:
                    continue
                except ChannelClosed:
                    return
                if m.get("op") == "ping":
                    ch_child.send({"id": m["id"], "ok": True,
                                   "result": {"pong": True}})
                # "hang" never answers: the deadline sweep's case

        threading.Thread(target=responder, daemon=True).start()
        rp = ReplicaProc(0, None, Channel(a))
        assert rp.call("ping", timeout_s=10)["pong"] is True
        f = rp.rpc("hang", timeout_s=0.15)
        assert isinstance(f.exception(timeout=10), IpcTimeoutError)
        rp.quarantine(ReplicaDeadError(f"gate teardown {tag}"))
        stop.set()

    assert not obs.ENABLED
    exercise("off")
    assert obs.registry.empty()  # disabled: zero bookkeeping

    obs.enable(install_hooks=False)
    try:
        obs.reset()
        exercise("on")
        g = obs.registry.get_counter
        assert obs.registry.get_histogram(
            "serve.procfleet.rpc_latency_s", op="ping"
        )["count"] == 1
        assert g("serve.procfleet.ipc_timeouts", op="hang") == 1
        assert g("serve.procfleet.quarantined", replica=0) == 1
    finally:
        obs.disable()
        obs.reset()


def test_round18_fleet_obs_gated(tmp_path):
    """ISSUE 16: the round-18 fleet-observability plane — IPC channel
    accounting, per-replica deadline misses, the supervision timeline
    — is emitted under obs and costs NOTHING when disabled: no
    registry series, no fleetlog file, no flight-recorder traffic.
    Same stub-responder topology as the round-17 gate (the gate
    measures the router's bookkeeping, not subprocess boot)."""
    import socket
    import threading
    import types

    from combblas_tpu.obs.fleetlog import FleetLog
    from combblas_tpu.obs.recorder import FlightRecorder
    from combblas_tpu.serve.frame import Channel, ChannelClosed
    from combblas_tpu.serve.procfleet import (
        IpcTimeoutError,
        ProcessFleet,
        ReplicaDeadError,
        ReplicaProc,
    )

    def exercise(tag):
        a, b = socket.socketpair()
        stop = threading.Event()
        ch_child = Channel(b)

        def responder():
            while not stop.is_set():
                try:
                    m = ch_child.recv(timeout=0.05)
                except socket.timeout:
                    continue
                except ChannelClosed:
                    return
                if m.get("op") == "ping":
                    ch_child.send({"id": m["id"], "ok": True,
                                   "result": {"pong": True}})
                # "hang" never answers: the deadline sweep's case

        threading.Thread(target=responder, daemon=True).start()
        rp = ReplicaProc(0, None, Channel(a, peer="replica0"))
        assert rp.call("ping", timeout_s=10)["pong"] is True
        f = rp.rpc("hang", timeout_s=0.15)
        assert isinstance(f.exception(timeout=10), IpcTimeoutError)
        # the supervisor's event hook over a stub fleet: the gate must
        # keep the fleetlog file AND the recorder ring untouched
        stub = types.SimpleNamespace(
            replicas=[rp],
            fleetlog=FleetLog(str(tmp_path / f"fleet-{tag}.jsonl")),
            recorder=FlightRecorder(
                out_dir=str(tmp_path / f"rec-{tag}")),
        )
        ProcessFleet._fleet_event(
            stub, "quarantine", replica=0, reason="gate"
        )
        rp.quarantine(ReplicaDeadError(f"gate teardown {tag}"))
        stop.set()
        return stub

    assert not obs.ENABLED
    stub = exercise("off")
    assert obs.registry.empty()  # disabled: zero bookkeeping
    assert not os.path.exists(stub.fleetlog.path)  # no timeline file
    assert stub.recorder.recorded == 0  # no recorder traffic

    obs.enable(install_hooks=False)
    try:
        obs.reset()
        stub = exercise("on")
        g = obs.registry.get_counter
        # channel accounting: both directions, framed byte counts
        assert g("serve.ipc.bytes_out", peer="replica0") > 0
        assert g("serve.ipc.bytes_in", peer="replica0") > 0
        assert obs.registry.get_histogram(
            "serve.ipc.encode_s", peer="replica0"
        )["count"] >= 2  # ping + hang
        assert obs.registry.get_histogram(
            "serve.ipc.decode_s", peer="replica0"
        )["count"] >= 1  # pong
        assert g("serve.ipc.deadline_missed", replica=0) == 1
        # supervision timeline: ring + file + counter + dump
        assert g("serve.fleetlog.events", event="quarantine") == 1
        (ev,) = stub.fleetlog.snapshot()
        assert ev["name"] == "fleet.quarantine"
        assert ev["reason"] == "gate"
        assert os.path.exists(stub.fleetlog.path)
        assert stub.recorder.dumps == 1  # quarantine dumps the ring
    finally:
        obs.disable()
        obs.reset()


def test_fleetlog_jsonl_roundtrip(tmp_path):
    """ISSUE 16 satellite: the supervision timeline is an ordinary
    ``combblas_tpu.fleetlog/v1`` JSONL file — every line passes
    ``validate_record`` via ``parse_jsonl``, reserved envelope fields
    are remapped (never clobbered), and both the ring and the file are
    bounded."""
    from combblas_tpu.obs.fleetlog import FleetLog

    path = str(tmp_path / "fl" / "fleetlog.jsonl")
    fl = FleetLog(path, capacity=4, max_file_events=5, tenant="t0")
    assert not os.path.exists(path)  # lazy: idle fleet leaves no file
    for i in range(7):
        fl.event("spawn", replica=i, kind="oops", ts="clash")
    recs = obs.parse_jsonl(path)  # validate=True: schema-checked
    assert recs[0]["kind"] == "meta"
    assert recs[0]["schema"] == obs.FLEETLOG_SCHEMA
    events = [r for r in recs if r["kind"] == "event"]
    assert len(events) == 5  # file capped at max_file_events
    assert events[0]["name"] == "fleet.spawn"
    assert events[0]["tenant"] == "t0"
    # reserved names remapped, discriminators intact
    assert events[0]["f_kind"] == "oops"
    assert events[0]["f_ts"] == "clash"
    # ring keeps rotating past the file cap, oldest first
    assert [e["replica"] for e in fl.snapshot()] == [3, 4, 5, 6]
    d = fl.describe()
    assert d["recorded"] == 7 and d["file_events"] == 5
    assert d["truncated"] and d["write_errors"] == 0


def test_round21_shard_wire_counters_gated():
    """ISSUE 19 satellite: the round-21 sharded wire-protocol series —
    per-fan payload bytes by direction and encoding, per-hop frontier
    nnz, the router's encoding decision — are emitted under obs and
    cost NOTHING when disabled.  A tiny 2-slice LOCAL engine keeps the
    gate tier-1 cheap (warmup=False: trace counters are someone else's
    gate)."""
    import numpy as np

    from combblas_tpu.serve import ShardedEngine

    n = 24
    rng = np.random.default_rng(5)
    rows = rng.integers(0, n, 90)
    cols = rng.integers(0, n, 90)
    srcs = np.array([0, 7], np.int32)

    def exercise(tag):
        eng = ShardedEngine.build(
            rows, cols, nrows=n, nslices=2, kinds=("bfs",),
            warmup=False, frontier="auto",
        )
        eng.execute("bfs", srcs)
        eng.close()
        return eng

    assert not obs.ENABLED
    exercise("off")
    assert obs.registry.empty()  # disabled: zero bookkeeping

    obs.enable(install_hooks=False)
    try:
        obs.reset()
        eng = exercise("on")
        st = eng.last_exec_stats
        assert st["hops"] >= 1 and st["collects"] == 1
        g = obs.registry.get_counter
        # every fan accounts both directions; labels partition by
        # encoding (sparse/dense frontier hops + the collect fan)
        by_enc = {
            e: g("serve.shard.hop_bytes", direction="out", encoding=e)
            + g("serve.shard.hop_bytes", direction="in", encoding=e)
            for e in ("sparse", "dense", "collect")
        }
        assert by_enc["collect"] > 0
        assert sum(by_enc.values()) == st["bytes_out"] + st["bytes_in"]
        assert by_enc == st["bytes_by_enc"] | {
            e: 0 for e in by_enc if e not in st["bytes_by_enc"]
        }
        # the router's per-hop decision + frontier size distribution
        assert sum(
            g("serve.shard.encoding", choice=c)
            for c in ("sparse", "dense")
        ) == st["hops"]
        h = obs.registry.get_histogram(
            "serve.shard.frontier_nnz", kind="bfs"
        )
        assert h["count"] == st["hops"]
        assert h["max"] == max(st["frontier_nnz"])
    finally:
        obs.disable()
        obs.reset()
