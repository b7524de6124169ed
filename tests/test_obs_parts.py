"""PR 23: the engine's parts of ``execute``, the readback / scatter byte
counters, the named scopes of the two batch BFS programs, and the names
that keep a scoped program out of its unscoped parent's compile-cache
entry (docs/observability.md "Where a served batch spends its time")."""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu import obs
from combblas_tpu.models import PAD_ROOT
from combblas_tpu.models import bfs as bfs_mod
from combblas_tpu.obs import opnames
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import GraphEngine, ServeConfig, batcher

N = 512  # scale 9


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    obs.trace.set_sample_rate(0.0)
    yield
    obs.disable()
    obs.reset()
    obs.trace.set_sample_rate(None)


def _engine(shape=(1, 1), seed=0):
    rng = np.random.default_rng(seed)
    r = rng.integers(0, N, 4000)
    c = rng.integers(0, N, 4000)
    return GraphEngine.from_coo(
        Grid.make(*shape), np.concatenate([r, c]), np.concatenate([c, r]),
        N, kinds=("bfs",),
    )


@pytest.fixture(scope="module")
def engine():
    return _engine()


def _serve(engine, count=20):
    srv = engine.serve(ServeConfig(lane_widths=(1, 16)))
    srv.warmup(kinds=("bfs",), widths=(1, 16))
    srv.start()
    try:
        for f in [srv.submit("bfs", i) for i in range(count)]:
            f.result(timeout=120)
    finally:
        srv.close()
    return obs.trace.records()


# --- parts ------------------------------------------------------------------


@pytest.mark.parametrize("on,rate,want", [
    (True, 1.0, True),     # telemetry on, every request sampled
    (True, 0.0, False),    # on, none sampled: no trace, so no parts
    (False, 1.0, False),   # off: nothing at all
])
def test_parts_telescope_to_execute_and_only_when_traced(
        engine, on, rate, want):
    if on:
        obs.enable(install_hooks=False)
    obs.trace.set_sample_rate(rate)
    recs = _serve(engine)
    assert bool(recs) == want
    for rec in recs:
        ex = next(s for s in rec["stages"] if s["stage"] == "execute")
        # a batch that started its successor at the hand-off (the four
        # requests left over, once due) carries the fifth part
        assert [p["stage"] for p in ex["parts"] if p["stage"] != "handoff"
                ] == ["launch", "device", "readback", "to_global"]
        assert all(p["s"] >= 0 for p in ex["parts"])
        # parts sum to the stage as stages sum to the wall (each number
        # is rounded to a nanosecond on its own)
        assert abs(sum(p["s"] for p in ex["parts"]) - ex["s"]) < 1e-8
        assert abs(sum(s["s"] for s in rec["stages"]) - rec["wall_s"]) < 1e-8
        # every mark of the process on one monotonic clock
        assert 0 < rec["t0"] <= time.perf_counter()
        obs.validate_record(dict(rec, v=obs.SCHEMA_VERSION, kind="trace"))
    if not want:
        # and without a traced member the engine is handed no list: the
        # host plane gets no serve.execute.* annotation either
        assert not [r for r in obs.registry.snapshot()
                    if r["name"] == "serve.trace.sampled"]


def test_failed_attempt_keeps_parts_telescoping(engine):
    """A batch that fails in ``execute`` charges the attempt to the part
    ``failed``; the retry adds its own four parts to the same stage."""
    obs.enable(install_hooks=False)
    obs.trace.set_sample_rate(1.0)
    srv = engine.serve(ServeConfig(lane_widths=(1, 16)))
    srv.warmup(kinds=("bfs",), widths=(1, 16))
    srv.faults.script("engine.execute", at=(0,))
    fut = srv.submit("bfs", 3)
    srv.pump(force=True)
    fut.result(timeout=60)
    srv.close()
    (rec,) = obs.trace.records()
    ex = next(s for s in rec["stages"] if s["stage"] == "execute")
    names = [p["stage"] for p in ex["parts"]]
    assert names[0] == "failed" and set(names) == {
        "failed", "launch", "device", "readback", "to_global"}
    assert abs(sum(p["s"] for p in ex["parts"]) - ex["s"]) < 1e-8


def test_execute_with_obs_off_reads_no_clock_and_waits_for_nothing(
        engine, monkeypatch):
    sources = np.arange(16, dtype=np.int32)
    engine.warmup(kinds=("bfs",), widths=(16,))
    want = engine.execute("bfs", sources)
    calls = {"clock": 0, "block": 0}
    real_clock, real_block = time.perf_counter, jax.block_until_ready

    def clock():
        calls["clock"] += 1
        return real_clock()

    def block(x):
        calls["block"] += 1
        return real_block(x)

    monkeypatch.setattr(time, "perf_counter", clock)
    monkeypatch.setattr(jax, "block_until_ready", block)
    assert not obs.ENABLED
    # a list handed in with telemetry off stays empty too
    parts = []
    got = engine.execute("bfs", sources, parts)
    assert calls == {"clock": 0, "block": 0} and parts == []
    np.testing.assert_array_equal(got["levels"], want["levels"])
    assert obs.registry.empty() and obs._spans.empty()
    # on, with a list: four clock reads and one wait
    obs.enable(install_hooks=False)
    engine.execute("bfs", sources, parts)
    assert [p for p, _ in parts] == [
        "launch", "device", "readback", "to_global"]
    assert calls["block"] == 1
    assert [t for _, t in parts] == sorted(t for _, t in parts)
    # on, without a list (warm-up, a direct caller): no wait, no parts
    before = dict(calls)
    engine.execute("bfs", sources)
    assert calls["block"] == before["block"]


# --- the hand-off (PR 27) ---------------------------------------------------


def _pump_full_lanes(engine, lanes):
    """``lanes`` full 16-wide batches through a worker-less ``pump()``;
    returns the trace records grouped by batch, in pop order."""
    srv = engine.serve(ServeConfig(lane_widths=(1, 16), max_wait_s=60.0))
    srv.warmup(kinds=("bfs",), widths=(16,))
    futs = [srv.submit("bfs", i) for i in range(16 * lanes)]
    assert srv.pump() == lanes
    assert all(f.done() for f in futs)
    srv.close()
    groups = {}
    for rec in obs.trace.records():
        ex = next(s for s in rec["stages"] if s["stage"] == "execute")
        wait = next(s for s in rec["stages"] if s["stage"] == "queue_wait")
        groups.setdefault(ex["s"], (rec["t0"] + wait["s"], rec, ex))
    return [g[1:] for g in sorted(groups.values(), key=lambda g: g[0])]


@pytest.mark.parametrize("lanes", [1, 3])
def test_handoff_part_and_overlapped_counter(engine, lanes):
    """A batch that started its successor between ``device`` and
    ``readback`` carries the part ``handoff``; one with nothing due
    keeps the four parts; either way parts sum to the stage, and
    ``serve.batch.overlapped`` counts the batches read back under a
    successor: all but the last."""
    obs.enable(install_hooks=False)
    obs.trace.set_sample_rate(1.0)
    batches = _pump_full_lanes(engine, lanes)
    assert len(batches) == lanes
    four = ["launch", "device", "readback", "to_global"]
    for k, (rec, ex) in enumerate(batches):
        names = [p["stage"] for p in ex["parts"]]
        last = k == lanes - 1
        assert names == (four if last else four[:2] + ["handoff"] + four[2:])
        assert all(p["s"] >= 0 for p in ex["parts"])
        assert abs(sum(p["s"] for p in ex["parts"]) - ex["s"]) < 1e-8
        assert abs(sum(s["s"] for s in rec["stages"]) - rec["wall_s"]) < 1e-8
    assert (_counter("serve.batch.overlapped", kind="bfs") or 0) == lanes - 1


def test_handoff_leaves_nothing_with_telemetry_off(engine):
    obs.trace.set_sample_rate(1.0)
    assert _pump_full_lanes(engine, 2) == []
    assert obs.registry.empty() and obs._spans.empty()


# --- counters ---------------------------------------------------------------


def _counter(name, **labels):
    return obs.registry.get_counter(name, **labels)


def test_byte_counters_count_what_numpy_says(engine):
    obs.enable(install_hooks=False)
    engine.warmup(kinds=("bfs",), widths=(16,))
    sources = np.full(16, PAD_ROOT, np.int32)
    sources[:5] = [3, 9, 27, 81, 243]
    result = engine.execute("bfs", sources)
    blocks = 2 * N * 16 * 4  # parents and levels, int32 [n, 16]
    assert result["parents"].nbytes + result["levels"].nbytes == blocks
    assert _counter("serve.readback.bytes", kind="bfs", width=16) == blocks

    from concurrent.futures import Future

    reqs = [batcher.Request(rid=i, kind="bfs", root=int(sources[i]),
                            future=Future(), submitted_at=time.monotonic())
            for i in range(5)]
    assert batcher.scatter(reqs, result) == 5
    # the same batch by hand: a lane that shares memory with the batch
    # buffer was handed out as a view, any other was copied
    copied = views = 0
    for k, req in enumerate(reqs):
        lane = req.future.result()
        for key in ("parents", "levels"):
            np.testing.assert_array_equal(lane[key], result[key][:, k])
            if np.shares_memory(lane[key], result[key]):
                views += 1
            else:
                copied += lane[key].nbytes
        assert lane["batch_niter"] == result["batch_niter"]
    assert copied + views * N * 4 == 5 * 2 * N * 4
    assert _counter("serve.scatter.copied_bytes", kind="bfs") == copied
    assert _counter("serve.scatter.views", kind="bfs") == views
    # lane-major results (how the chip hands them over) are all views
    obs.reset()
    major = {k: np.asfortranarray(v) if isinstance(v, np.ndarray) else v
             for k, v in result.items()}
    for r in reqs:
        r.future = Future()
    batcher.scatter(reqs, major)
    assert _counter("serve.scatter.copied_bytes", kind="bfs") == 0
    assert _counter("serve.scatter.views", kind="bfs") == 10
    # off: not one series
    obs.disable()
    obs.reset()
    for r in reqs:
        r.future = Future()
    batcher.scatter(reqs, engine.execute("bfs", sources))
    assert obs.registry.empty()


# --- scopes -----------------------------------------------------------------


def _op_names(lowered) -> set:
    """``op_name`` metadata of the compiled program (nested jits inlined,
    so a path runs from the program's name down to the primitive)."""
    return set(opnames.parse(lowered.compile().as_text())[1].values())


def _scope_components(names) -> set:
    return {c for nm in names for c in nm.split("/")}


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
@pytest.mark.parametrize("program", ["served", "compact"])
def test_every_documented_scope_is_in_the_lowered_program(shape, program):
    eng = _engine(shape)
    sources = jnp.arange(16, dtype=jnp.int32)
    if program == "served":
        lowered = eng.plan("bfs", 16).lower(sources)
    else:
        lowered = bfs_mod._bfs_batch_compact_program.lower(eng.E, sources)
    names = _op_names(lowered)
    found = _scope_components(names)
    want = set(bfs_mod.BFS_SCOPES) - {"ell.bucket<i>"}
    # each program has one scope the other lacks: level 0 as a push is
    # the served plan's, the parents pass the compact program's
    want -= {"bfs.parents"} if program == "served" else {"bfs.push"}
    assert want <= found, sorted(want - found)
    nb = len(eng.E.buckets)
    assert {f"ell.bucket{i}" for i in range(nb)} <= found
    # the level loop holds its phases: a bucket's gather lies under it
    assert any(
        re.search(r"bfs\.level/.*ell\.bucket0/gather", nm)
        for nm in names
    )


def test_warmup_publishes_op_names_only_with_telemetry_on():
    eng = _engine()
    eng.warmup(kinds=("bfs",), widths=(16,))
    assert opnames.tables() == {}
    traces = eng.stats()["plans"]["bfs/16"]["traces"]
    obs.enable(install_hooks=False)
    eng.warmup(kinds=("bfs",), widths=(16,))
    table = opnames.tables()["jit_serve_bfs_w16"]
    assert any("bfs.level" in v and "ell.bucket0/gather" in v
               for v in table.values())
    # publishing lowered the program again; that is no retrace
    assert eng.stats()["plans"]["bfs/16"]["traces"] == traces
    assert _counter("trace.serve", kind="bfs", width=16) == 0
    # the library entry publishes on its first traced call
    bfs_mod.bfs_batch_compact(eng.E, jnp.arange(16, dtype=jnp.int32))
    compact = opnames.tables()["jit__bfs_batch_compact_program"]
    assert any("bfs.parents" in v for v in compact.values())
    obs.reset()
    assert opnames.tables() == {}


def test_opnames_parse_reads_module_and_instructions():
    text = (
        "HloModule jit_serve_bfs_w16, is_scheduled=true\n\n"
        "%fused (p: s32[4]) -> s32[4] {\n"
        '  ROOT %max.1 = s32[4]{0} maximum(%p, %p), '
        'metadata={op_name="reduce_max"}\n}\n\n'
        "ENTRY %main {\n"
        '  %fusion.249 = s32[8,16]{0,1:T(8,128)} fusion(%a), kind=kLoop, '
        'calls=%fused, metadata={op_name="jit(f)/bfs.level/while/body/'
        'ell.bucket3/gather/gather" stack_frame_id=24}\n'
        "  %copy.3 = s32[8,16]{1,0} copy(%fusion.249)\n}\n"
    )
    name, table = opnames.parse(text)
    assert name == "jit_serve_bfs_w16"
    assert table == {
        "max.1": "reduce_max",
        "fusion.249":
            "jit(f)/bfs.level/while/body/ell.bucket3/gather/gather",
    }


def _cache_key(fn, *args):
    """JAX's persistent-cache key of ``jit(fn)`` on ``args``."""
    from jax._src import cache_key, compiler
    from jax._src.interpreters import mlir  # noqa: F401
    from jax._src.lib.mlir import ir  # noqa: F401

    lowered = jax.jit(fn).lower(*args)
    backend = jax.devices()[0].client
    module = lowered.compiler_ir("stablehlo")
    opts = compiler.get_compile_options(num_replicas=1, num_partitions=1)
    return cache_key.get(
        module, np.asarray(jax.devices()[:1]), opts, backend,
    )


def test_a_scope_alone_keeps_the_cache_key_and_a_name_changes_it():
    """JAX's persistent-cache key strips metadata: a program that gained
    only scopes would be served its parent's executable, without them,
    wherever the parent ran first.  So every program that gained scopes
    gained a name too; this holds the reason and the names."""
    x = jnp.arange(8)

    def impl(x):
        return x * 2 + 1

    def scoped(x):
        with jax.named_scope("bfs.level"):
            return x * 2 + 1

    scoped.__name__ = scoped.__qualname__ = "impl"

    def serve_bfs_w16(x):
        with jax.named_scope("bfs.level"):
            return x * 2 + 1

    plain = _cache_key(impl, x)
    assert _cache_key(scoped, x) == plain       # the trap
    assert _cache_key(serve_bfs_w16, x) != plain  # the way out
    # and the two programs the cells run carry their new names
    eng = _engine()
    text = eng.plan("bfs", 16).lower(
        jnp.arange(16, dtype=jnp.int32)).as_text()
    assert "module @jit_serve_bfs_w16" in text
    text = bfs_mod._bfs_batch_compact_program.lower(
        eng.E, jnp.arange(16, dtype=jnp.int32)).as_text()
    assert "module @jit__bfs_batch_compact_program" in text
