"""Query-serving subsystem (combblas_tpu/serve): lane bucketing,
pad-sentinel hygiene, request/result mapping under concurrency,
backpressure, error isolation, warm-plan zero-retrace contract, and the
compile-cache idempotence satellite.

The batcher property tests are the acceptance gate for the serving
PR: arbitrary arrival counts round to the correct power-of-two bucket,
padded lanes never leak into user results, and results map back to the
right request ids even under concurrent ``submit()``.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu import obs
from combblas_tpu.models import PAD_ROOT
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import (
    BackpressureError,
    GraphEngine,
    ServeConfig,
    bucket_width,
)
from combblas_tpu.serve.batcher import assemble
from combblas_tpu.utils.rmat import rmat_symmetric_coo


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


SCALE = 7
N = 1 << SCALE


@pytest.fixture(scope="module")
def graph():
    rows, cols = rmat_symmetric_coo(jax.random.key(3), SCALE, 8)
    return np.asarray(rows), np.asarray(cols)


@pytest.fixture(scope="module")
def engine(graph):
    rows, cols = graph
    # explicit kinds: sssp over the unweighted graph (unit weights) is
    # intentional here — the default would exclude it (no weights=)
    return GraphEngine.from_coo(
        Grid.make(2, 2), rows, cols, N,
        kinds=("bfs", "sssp", "pagerank", "bc"),
    )


def test_default_kinds_exclude_unweighted_sssp(graph):
    rows, cols = graph
    eng = GraphEngine.from_coo(Grid.make(1, 1), rows, cols, N)
    assert "sssp" not in eng.kinds()  # no weights: hop counts are not
    assert "bfs" in eng.kinds()       # distances — opt in explicitly


def test_bc_symmetry_claim_is_verified():
    """symmetric=True (bc reuses E as its own transpose) is CHECKED at
    load: a directed COO must not silently serve wrong BC scores."""
    rows = np.array([0, 1, 2], np.int64)  # 0->1->2->3 chain, one-way
    cols = np.array([1, 2, 3], np.int64)
    with pytest.raises(ValueError, match="not structurally symmetric"):
        GraphEngine.from_coo(Grid.make(1, 1), cols, rows, 4)
    # symmetric=False builds the real transpose instead
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), cols, rows, 4, symmetric=False,
    )
    assert eng.ET is not eng.E


@pytest.fixture(scope="module")
def live_roots(graph):
    rows, _ = graph
    deg = np.bincount(rows, minlength=N)
    return np.flatnonzero(deg > 0).astype(np.int32)


# --- batcher ----------------------------------------------------------------


def test_bucket_width_rounds_to_power_of_two():
    """Property: any arrival count lands on the smallest configured
    bucket that fits it (and clamps to the widest past the end)."""
    widths = (1, 2, 4, 8, 16)
    for count in range(1, 40):
        w = bucket_width(count, widths)
        if count <= 16:
            assert w >= count, (count, w)
            assert w in widths
            # minimality: no smaller configured width fits
            smaller = [x for x in widths if x < w]
            assert all(x < count for x in smaller), (count, w)
            assert w == 1 << (count - 1).bit_length()
        else:
            assert w == 16
    with pytest.raises(ValueError):
        bucket_width(0, widths)


def test_assemble_pads_with_sentinel():
    from combblas_tpu.serve.batcher import Request
    from concurrent.futures import Future

    reqs = [
        Request(rid=i, kind="bfs", root=10 + i, future=Future(),
                submitted_at=0.0)
        for i in range(5)
    ]
    src = assemble(reqs, (1, 2, 4, 8))
    assert src.shape == (8,)
    np.testing.assert_array_equal(src[:5], [10, 11, 12, 13, 14])
    assert (src[5:] == PAD_ROOT).all()


def test_pad_root_exported_and_inert(engine, live_roots):
    """models.PAD_ROOT is the public lane-padding sentinel; a PAD_ROOT
    lane discovers nothing / carries no mass in every batch kernel."""
    assert PAD_ROOT == -1
    srcs = np.array([live_roots[0], PAD_ROOT, live_roots[1]], np.int32)
    r = engine.execute("bfs", srcs)
    assert (r["parents"][:, 1] == -1).all()
    assert (r["levels"][:, 1] == -1).all()
    r = engine.execute("pagerank", srcs)
    assert r["ranks"][:, 1].sum() == 0.0
    np.testing.assert_allclose(r["ranks"][:, 0].sum(), 1.0, rtol=1e-4)
    r = engine.execute("sssp", srcs)
    assert np.isinf(r["dist"][:, 1]).all()
    r = engine.execute("bc", srcs)
    assert (r["scores"][:, 1] == 0).all()


# --- engine correctness -----------------------------------------------------


def test_served_results_match_direct_kernels(engine, graph, live_roots):
    """Each serve kind's lanes equal the direct kernel's answer."""
    from combblas_tpu.models.bc import bc_batch_dense
    from combblas_tpu.models.bfs import bfs
    from combblas_tpu.models.pagerank import pagerank_batch
    from combblas_tpu.models.sssp import sssp

    srcs = live_roots[[0, 3, 11]]
    r = engine.execute("bfs", srcs)
    for k, s in enumerate(srcs):
        _, l1, _ = bfs(engine.E, int(s))
        np.testing.assert_array_equal(r["levels"][:, k], l1.to_global())

    r = engine.execute("sssp", srcs)
    d1, _ = sssp(engine.E_weighted, int(srcs[1]))
    np.testing.assert_allclose(r["dist"][:, 1], d1.to_global(), rtol=1e-5)

    r = engine.execute("pagerank", srcs)
    pr_direct, _ = pagerank_batch(
        engine.P_ell, jnp.asarray(srcs), engine.dangling
    )
    np.testing.assert_allclose(
        r["ranks"], pr_direct.to_global(), rtol=1e-5
    )

    # bc: lanes match the public per-lane wrapper, and their sum
    # reproduces the batch total exactly
    from combblas_tpu.models.bc import bc_batch_dense_lanes

    r = engine.execute("bc", srcs)
    lanes = bc_batch_dense_lanes(engine.E, engine.ET, jnp.asarray(srcs))
    np.testing.assert_allclose(
        r["scores"], lanes.to_global(), rtol=1e-5, atol=1e-6
    )
    total = bc_batch_dense(engine.E, engine.ET, jnp.asarray(srcs))
    np.testing.assert_allclose(
        r["scores"].sum(axis=1), total.to_global(), rtol=1e-4, atol=1e-4
    )


def test_warm_plans_never_retrace(engine, live_roots):
    """The zero-retrace contract: after warmup() over the lane buckets,
    serving any mix inside (kinds x widths) performs no traces — the
    obs ``trace.serve`` counter and the engine's host counter agree."""
    obs.enable(install_hooks=False)
    engine.warmup(kinds=("bfs", "pagerank"), widths=(1, 4))
    mark = engine.trace_mark()
    t0 = obs.registry.get_counter("trace.serve", kind="bfs", width=4)
    for batch in (live_roots[:4], live_roots[4:8], live_roots[2:6]):
        engine.execute("bfs", batch[:4])
        engine.execute("pagerank", batch[:4])
        engine.execute("bfs", np.asarray([batch[0]], np.int32))
    assert engine.retraces_since(mark) == 0
    assert (
        obs.registry.get_counter("trace.serve", kind="bfs", width=4) == t0
    )


def test_plan_cache_hit_miss_counters(graph):
    rows, cols = graph
    obs.enable(install_hooks=False)
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), rows, cols, N, kinds=("bfs",)
    )
    eng.execute("bfs", np.asarray([1], np.int32))  # miss (build)
    eng.execute("bfs", np.asarray([1], np.int32))  # hit
    assert obs.registry.get_counter(
        "serve.plan_cache.misses", kind="bfs", width=1
    ) == 1
    assert obs.registry.get_counter(
        "serve.plan_cache.hits", kind="bfs", width=1
    ) == 1
    assert eng.stats()["plans"]["bfs/1"]["executions"] == 2
    # an engine only serves the kinds it was BUILT with: bc's transpose
    # (etc.) may not exist, so the kind is rejected, never approximated
    assert eng.kinds() == ("bfs",)
    with pytest.raises(ValueError, match="not built for kind"):
        eng.execute("bc", np.asarray([1], np.int32))
    with pytest.raises(ValueError, match="unknown query kind"):
        eng.serve().submit("sssp", 1)


def test_close_drains_without_started_worker(engine, live_roots):
    """close(drain=True) on a server whose worker never started must
    still execute the queue — futures may not hang forever."""
    srv = engine.serve(ServeConfig(lane_widths=(4,), max_wait_s=60.0))
    f = srv.submit("bfs", int(live_roots[0]))
    srv.close()  # no start(): the caller's thread drains
    assert f.result(timeout=0)["levels"][int(live_roots[0])] == 0


def test_submit_many_generator_keeps_future_per_root(engine, live_roots):
    """submit_many over a GENERATOR returns exactly one future per
    yielded root, in order, even when backpressure cuts it short."""
    srv = engine.serve(ServeConfig(
        lane_widths=(16,), max_queue=2, max_wait_s=60.0,
    ))  # worker never started: nothing drains
    roots = [int(r) for r in live_roots[:5]]
    futs = srv.submit_many("bfs", (r for r in roots))
    assert len(futs) == len(roots)
    # first 2 admitted (still pending: no worker), rest rejected
    assert [f.done() for f in futs] == [False, False, True, True, True]
    assert all(
        isinstance(f.exception(timeout=0), BackpressureError)
        for f in futs[2:]
    )
    srv.scheduler.fail_pending(RuntimeError("test teardown"))


def test_csc_companion_lives_with_the_version(graph):
    """An engine that serves BFS builds the CSC companion with its
    matrices (no ``keep_coo`` needed: ``from_coo`` has the edges in
    hand); an engine that does not serve BFS has none.  One that is not
    current is rebuilt from the retained COO, which stays (the write
    lane merges into it); without the COO there is nothing to rebuild
    from."""
    rows, cols = graph
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), rows, cols, N, kinds=("bfs",), keep_coo=True
    )
    csc = eng.csc_companion()
    assert len(csc) == 2 and csc is eng.version.csc
    assert eng.version.csc_current and eng._host_coo is not None
    eng.version.csc_current = False  # as a structural merge leaves it
    rebuilt = eng.csc_companion()
    assert rebuilt is not csc and eng.version.csc_current
    for a, b in zip(csc, rebuilt):
        assert a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))
    eng2 = GraphEngine.from_coo(
        Grid.make(1, 1), rows, cols, N, kinds=("bfs",)
    )
    assert eng2.csc_companion() is eng2.version.csc
    eng2.version.csc_current = False
    with pytest.raises(ValueError, match="keep_coo"):
        eng2.csc_companion()
    eng3 = GraphEngine.from_coo(
        Grid.make(1, 1), rows, cols, N,
        weights=np.ones(len(rows), np.float32), kinds=("sssp",),
    )
    assert eng3.version.csc is None


def test_scatter_returns_lane_copies(engine, live_roots):
    """Per-request results are COPIES, not views pinning the [n, W]
    batch buffer."""
    srv = engine.serve(ServeConfig(lane_widths=(4,), max_wait_s=0.01))
    f = srv.submit("bfs", int(live_roots[0]))
    srv.pump(force=True)
    res = f.result(timeout=0)
    assert res["levels"].base is None


# --- server: batching, mapping, isolation, backpressure ---------------------


def test_results_map_to_request_ids(engine, live_roots):
    """5 requests flush as one width-8 batch: every future gets ITS
    root's answer (ground truth per root), pad lanes reach nobody."""
    from combblas_tpu.models.bfs import bfs

    srv = engine.serve(ServeConfig(lane_widths=(8,), max_wait_s=0.01))
    srv.warmup(kinds=("bfs",), widths=(8,))
    roots = [int(r) for r in live_roots[[9, 1, 5, 13, 2]]]
    futs = {r: srv.submit("bfs", r) for r in roots}
    # worker not started: drive deterministically
    assert srv.pump(force=True) == 1  # ONE coalesced batch
    for r, f in futs.items():
        res = f.result(timeout=0)
        _, l1, _ = bfs(engine.E, r)
        np.testing.assert_array_equal(res["levels"], l1.to_global())
        assert res["levels"][r] == 0  # its own root, not a neighbor's
    assert srv.stats()["mean_occupancy"] == pytest.approx(5 / 8)


def test_concurrent_submit_maps_results(engine, live_roots):
    """Property: under concurrent submit() from many threads, every
    future still maps to its own request (levels[root] == 0 uniquely
    identifies the lane)."""
    engine.warmup(kinds=("bfs",), widths=(1, 2, 4, 8))
    srv = engine.serve(ServeConfig(
        lane_widths=(1, 2, 4, 8), max_wait_s=0.002,
    )).start()
    try:
        roots = [int(r) for r in live_roots[:24]]
        results: dict[int, object] = {}
        errs: list = []

        def worker(rs):
            try:
                for r in rs:
                    results[r] = srv.submit("bfs", r).result(timeout=60)
            except Exception as e:  # pragma: no cover - fail loudly
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(roots[i::4],))
            for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errs
        assert len(results) == len(roots)
        for r, res in results.items():
            assert res["levels"][r] == 0, r
            assert (res["parents"] != PAD_ROOT).any()
    finally:
        srv.close()


def test_backpressure_rejects_when_full(engine, live_roots):
    """A full queue must REJECT with a retry-after hint, not block."""
    srv = engine.serve(ServeConfig(
        lane_widths=(16,), max_queue=3, max_wait_s=7.5,
    ))  # worker never started: nothing drains
    for r in live_roots[:3]:
        srv.submit("bfs", int(r))
    with pytest.raises(BackpressureError) as ei:
        srv.submit("bfs", int(live_roots[3]))
    assert ei.value.retry_after_s == pytest.approx(7.5)
    assert srv.stats()["rejected"] == 1
    # submit_many: admitted prefix + failed remainder, nothing lost
    futs = srv.submit_many("bfs", [int(r) for r in live_roots[4:7]])
    assert len(futs) == 3
    assert all(
        isinstance(f.exception(timeout=0), BackpressureError)
        for f in futs
    )
    srv.scheduler.fail_pending(RuntimeError("test teardown"))


def test_malformed_root_fails_request_not_batch(engine, live_roots):
    """Error isolation: a bad root's future carries the ValueError; its
    batch-mates complete normally."""
    srv = engine.serve(ServeConfig(lane_widths=(4,), max_wait_s=0.01))
    good = [int(r) for r in live_roots[:3]]
    f_good = [srv.submit("bfs", r) for r in good]
    f_bad = srv.submit("bfs", N + 5)  # out of range
    f_bad2 = srv.submit("bfs", "not-a-root")  # wrong type entirely
    assert isinstance(f_bad.exception(timeout=0), ValueError)
    assert isinstance(f_bad2.exception(timeout=0), ValueError)
    srv.pump(force=True)
    for r, f in zip(good, f_good):
        assert f.result(timeout=0)["levels"][r] == 0
    # unknown KIND is a caller bug -> raises at the call site
    with pytest.raises(ValueError):
        srv.submit("nope", good[0])


def test_request_timeout_expires_in_queue(engine, live_roots):
    srv = engine.serve(ServeConfig(lane_widths=(4,), max_wait_s=60.0))
    f = srv.submit("bfs", int(live_roots[0]), timeout_s=0.001)
    time.sleep(0.01)
    srv.pump()  # deadline sweep happens before batching
    assert isinstance(f.exception(timeout=0), TimeoutError)


def test_timeout_callback_may_resubmit(engine, live_roots):
    """Futures settle OUTSIDE the scheduler lock: a done-callback that
    re-enters submit() (the retry pattern retry_after_s invites) must
    not deadlock the sweep."""
    srv = engine.serve(ServeConfig(lane_widths=(4,), max_wait_s=60.0))
    f = srv.submit("bfs", int(live_roots[0]), timeout_s=0.001)
    retried = []
    f.add_done_callback(
        lambda _f: retried.append(srv.submit("bfs", int(live_roots[0])))
    )
    time.sleep(0.01)
    done = threading.Event()

    def sweep():
        srv.scheduler.pop_ready()
        done.set()

    t = threading.Thread(target=sweep, daemon=True)
    t.start()
    assert done.wait(10), "pop_ready deadlocked on re-entrant submit"
    assert isinstance(f.exception(timeout=0), TimeoutError)
    assert len(retried) == 1  # the retry was admitted
    srv.scheduler.fail_pending(RuntimeError("test teardown"))


def test_short_timeout_tightens_flush_deadline(engine, live_roots):
    """A timeout shorter than the kind's max-wait must pull the flush
    forward (dispatch at half the timeout budget) — not sleep until
    max_wait and expire the request in queue."""
    srv = engine.serve(ServeConfig(lane_widths=(4,), max_wait_s=60.0))
    t0 = time.monotonic()
    f = srv.submit("bfs", int(live_roots[0]), timeout_s=1.0)
    nd = srv.scheduler.next_deadline()
    assert nd is not None and nd - t0 < 1.0  # NOT the 60 s flush wait
    assert nd - t0 == pytest.approx(0.5, abs=0.1)  # half the budget
    # at the dispatch-by time the batch flushes (deterministic clock)
    ready = srv.scheduler.pop_ready(now=t0 + 0.6)
    assert ready
    srv._execute_batches(ready)
    assert f.done() and f.exception(timeout=0) is None


def test_closed_server_rejects_submit(engine, live_roots):
    """submit()/start() after close() must raise, never strand a
    future or spawn a worker that can never receive work."""
    srv = engine.serve(ServeConfig(lane_widths=(4,), max_wait_s=0.01))
    srv.close()
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit("bfs", int(live_roots[0]))
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit("bfs", N + 5)  # malformed root: same close semantics
    with pytest.raises(RuntimeError, match="closed"):
        srv.start()


@pytest.mark.slow
def test_serve_stress_throughput(engine, live_roots):
    """Stress/latency: 200 mixed queries through the threaded worker;
    everything completes, batches coalesce (occupancy > half), and the
    warm plans never retrace. Marked slow: tier-1 budget holds."""
    engine.warmup(kinds=("bfs", "pagerank"), widths=(1, 2, 4, 8, 16))
    mark = engine.trace_mark()
    srv = engine.serve(ServeConfig(
        lane_widths=(1, 2, 4, 8, 16), max_wait_s=0.005, max_queue=512,
    )).start()
    try:
        kinds = ["bfs", "pagerank"]
        futs = [
            srv.submit(kinds[i % 2], int(live_roots[i % len(live_roots)]))
            for i in range(200)
        ]
        done = [f.result(timeout=300) for f in futs]
        assert len(done) == 200
        st = srv.stats()
        assert st["completed"] == 200
        assert st["batches"] < 200  # batching actually happened
        assert engine.retraces_since(mark) == 0
    finally:
        srv.close()


# --- satellites -------------------------------------------------------------


def test_compile_cache_idempotent(tmp_path):
    """Second enable with the same dir is a no-op; a different dir
    raises cleanly (process-global jax config must not silently move)."""
    from combblas_tpu.utils import compile_cache as cc

    prior = cc._configured_dir
    cc._reset_for_tests()
    try:
        cc.enable_compile_cache(str(tmp_path / "a"))
        cc.enable_compile_cache(str(tmp_path / "a"))  # idempotent
        cc.enable_compile_cache()  # "ensure enabled": no-op, no raise
        assert cc._configured_dir == str(tmp_path / "a")
        with pytest.raises(ValueError, match="already enabled"):
            cc.enable_compile_cache(str(tmp_path / "b"))
        # entry-count gauge is published through the obs provider path
        obs.enable(install_hooks=False)
        probe = jax.jit(lambda v: v + 1)
        probe(jnp.arange(4)).block_until_ready()
        obs.metrics_snapshot()  # polls providers
        g = obs.registry.get_gauge(
            "compile_cache.entries", dir=str(tmp_path / "a")
        )
        assert g is not None and g >= 0
    finally:
        cc._reset_for_tests()
        import jax as _jax

        if prior is not None:
            # restore the process's committed dir for later tests
            _jax.config.update("jax_compilation_cache_dir", prior)
            cc._configured_dir = prior
        else:
            # fully de-configure: leaving the persistent cache pointed
            # at the (deleted) tmp dir would leak cache writes into it
            # for the rest of the session
            _jax.config.update("jax_compilation_cache_dir", None)
            _jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 1.0
            )
            _jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", 0
            )
            cc._configured_dir = None


# --- serve warmup widths -----------------------------------------------------


def _small_bfs_engine(seed):
    rng = np.random.default_rng(seed)
    n = 32
    rows = rng.integers(0, n, 100).astype(np.int64)
    cols = rng.integers(0, n, 100).astype(np.int64)
    return GraphEngine.from_coo(
        Grid.make(1, 1), np.concatenate([rows, cols]),
        np.concatenate([cols, rows]), n, kinds=("bfs",),
    )


def test_warmup_explicit_widths_unchanged():
    warmed = _small_bfs_engine(6).warmup(widths=(2, 4))
    assert set(warmed) == {("bfs", 2), ("bfs", 4)}


def test_warmup_default_widths():
    """One place decides which lanes are warmed: no ``widths`` means
    ``DEFAULT_WARMUP_WIDTHS`` and nothing else, whatever plan-cache
    misses came before."""
    eng = _small_bfs_engine(7)
    eng.plan("bfs", 32)  # a miss outside the default widths
    warmed = eng.warmup()
    assert set(warmed) == {
        ("bfs", w) for w in GraphEngine.DEFAULT_WARMUP_WIDTHS
    }
