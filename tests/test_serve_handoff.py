"""PR 27: the serving worker's order of calls.  A batch's successor is
started between the end of the batch's device work and its readback, so
the host's readback and scatter run under the device's next program
(docs/serving.md "The worker's order")."""

import os
import sys
import threading
import time

import numpy as np
import pytest

from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import GraphEngine, ServeConfig
from combblas_tpu.serve.scheduler import Scheduler

N = 512  # scale 9
W = 4


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(0)
    r = rng.integers(0, N, 4000)
    c = rng.integers(0, N, 4000)
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), np.concatenate([r, c]), np.concatenate([c, r]),
        N, kinds=("bfs",),
    )
    eng.warmup(kinds=("bfs",), widths=(1, 2, W))
    return eng


class Recording:
    """A ``GraphEngine`` that logs the worker's calls on it as
    ``(call, index of the launch)``; ``fail`` names calls that raise
    once, ``gate`` makes the first ``wait`` block until it is set."""

    def __init__(self, engine, fail=(), gate=None):
        self._engine = engine
        self.log = []
        self._index = {}
        self._handles = []  # kept alive, so their ids stay apart
        self._fail = set(fail)
        self._gate = gate
        self.waiting = threading.Event()

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def _note(self, call, handle):
        k = self._index[id(handle)]
        self.log.append((call, k))
        if (call, k) in self._fail:
            self._fail.discard((call, k))
            raise RuntimeError(f"injected: {call} of launch {k}")

    def launch(self, kind, sources, parts=None):
        handle = self._engine.launch(kind, sources, parts)
        self._index[id(handle)] = len(self._index)
        self._handles.append(handle)
        self._note("launch", handle)
        return handle

    def wait(self, handle):
        if self._gate is not None and self._index[id(handle)] == 0:
            self.waiting.set()
            assert self._gate.wait(60)
        self._engine.wait(handle)
        self._note("wait", handle)

    def collect(self, handle):
        self._note("collect", handle)
        return self._engine.collect(handle)


def _server(engine, **kw):
    """A worker-less server over a recording engine whose scheduler's
    pops are logged too: ``("pop", batches popped)``."""
    rec = Recording(engine, **kw)
    srv = rec.serve(ServeConfig(lane_widths=(1, 2, W), max_wait_s=60.0))
    srv.engine = rec
    pop = srv.scheduler.pop_ready

    def logged(*a, **k):
        out = pop(*a, **k)
        rec.log.append(("pop", len(out)))
        return out

    srv.scheduler.pop_ready = logged
    return srv, rec


def _submit(srv, count):
    return [srv.submit("bfs", 3 + i) for i in range(count)]


def _check(engine, futs, first=0):
    for i, f in enumerate(futs, first):
        got = f.result(timeout=0)
        want = engine.execute("bfs", np.array([3 + i], np.int32))
        np.testing.assert_array_equal(got["levels"], want["levels"][:, 0])
        np.testing.assert_array_equal(got["parents"], want["parents"][:, 0])


def test_successor_starts_between_device_end_and_readback(engine):
    srv, rec = _server(engine)
    futs = _submit(srv, 3 * W)
    assert srv.pump() == 3
    # pump() returns with nothing in hand and every future settled
    assert all(f.done() for f in futs)
    _check(engine, futs)
    log = rec.log
    at = {e: i for i, e in enumerate(log) if e[0] != "pop"}
    pops = [i for i, e in enumerate(log) if e == ("pop", 1)]
    assert len(pops) == 3 and ("pop", 2) not in log  # one at a time
    for k in (1, 2):
        # device-ready, then the pop, then the launch, then the readback
        assert (at[("wait", k - 1)] < pops[k] < at[("launch", k)]
                < at[("collect", k - 1)])
    # at most one batch launched ahead of the one being read back
    ahead = 0
    for call, _ in log:
        ahead += (call == "launch") - (call == "collect")
        assert ahead <= 2
    assert srv.stats()["batches"] == 3 and srv.retry_batches == 0
    srv.close()


def test_membership_is_not_decided_behind_a_running_program(engine):
    """While the batch in hand is on the device a full lane that has
    come due stays in the scheduler, open to late arrivals' order."""
    gate = threading.Event()
    srv, rec = _server(engine, gate=gate)
    srv.start()
    first = _submit(srv, W)
    assert rec.waiting.wait(60)
    later = _submit(srv, W)  # due at once: a full lane
    time.sleep(0.05)
    assert srv.scheduler.depth() == W
    assert [e for e in rec.log if e[0] == "launch"] == [("launch", 0)]
    gate.set()
    for f in first + later:
        f.result(timeout=60)
    # and it was launched before the first batch was read back
    assert rec.log.index(("launch", 1)) < rec.log.index(("collect", 0))
    srv.close()


@pytest.mark.parametrize("fail", [
    ("wait", 0), ("collect", 0), "batch.scatter",
])
def test_failure_in_hand_recovers_alone(engine, fail):
    """Batch 0 fails in its second half with batch 1 on the device:
    batch 0 alone goes through the bisection retrier, batch 1 settles
    from its own launch."""
    srv, rec = _server(engine, fail=[fail] if isinstance(fail, tuple) else ())
    if not isinstance(fail, tuple):
        srv.faults.script(fail, at=(0,))
    futs = _submit(srv, 2 * W)
    srv.pump()
    _check(engine, futs)
    st = srv.stats()
    assert st["per_kind"]["bfs"]["retried"] == W
    assert st["per_kind"]["bfs"]["poisoned"] == 0
    assert st["batches"] == 2 and st["retry_batches"] == 2
    launches = [k for c, k in rec.log if c == "launch"]
    assert launches == [0, 1, 2, 3]  # two halves of batch 0 retried
    assert ("collect", 1) in rec.log  # batch 1 settled from its own launch
    srv.close()


@pytest.mark.parametrize("point", ["engine.execute", "batch.assemble"])
def test_fault_on_successor_leaves_predecessor_delivered(engine, point):
    srv, rec = _server(engine)
    srv.faults.script(point, at=(1,))  # the second batch's start
    futs = _submit(srv, 2 * W)
    delivered = []
    futs[0].add_done_callback(
        lambda f: delivered.append([c for c, _ in rec.log].count("launch"))
    )
    srv.pump()
    _check(engine, futs)
    # batch 0 was delivered before any retry of batch 1 was launched
    assert delivered == [1]
    st = srv.stats()
    assert st["per_kind"]["bfs"]["retried"] == W
    assert st["retry_batches"] == 2 and st["per_kind"]["bfs"]["poisoned"] == 0
    srv.close()


@pytest.mark.parametrize("drain", [True, False])
def test_close_settles_the_launched_batch(engine, drain):
    gate = threading.Event()
    srv, rec = _server(engine, gate=gate)
    srv.start()
    futs = _submit(srv, 2 * W)
    assert rec.waiting.wait(60)
    closer = threading.Thread(target=srv.close, kwargs={"drain": drain})
    closer.start()
    while not srv._stop:
        time.sleep(0.001)
    gate.set()
    closer.join(60)
    assert not closer.is_alive()
    _check(engine, futs[:W])  # what was launched is finished
    # a stopping worker takes no successor: the rest is close()'s
    assert [e for e in rec.log if e[0] == "launch"][:2][-1] == (
        ("launch", 1) if drain else ("launch", 0))
    if drain:
        _check(engine, futs[W:], first=W)
    else:
        for f in futs[W:]:
            assert isinstance(f.exception(timeout=0), RuntimeError)


def test_halves_equal_execute_and_read_no_clock_when_off(
        engine, monkeypatch):
    sources = np.arange(W, dtype=np.int32) + 7
    want = engine.execute("bfs", sources)
    calls = {"clock": 0}
    real = time.perf_counter

    def clock():
        calls["clock"] += 1
        return real()

    monkeypatch.setattr(time, "perf_counter", clock)
    parts = []
    handle = engine.launch("bfs", sources, parts)
    engine.wait(handle)
    got = engine.collect(handle)
    assert calls["clock"] == 0 and parts == []
    assert set(got) == set(want)
    for key in ("parents", "levels"):
        np.testing.assert_array_equal(got[key], want[key])
    assert got["batch_niter"] == want["batch_niter"]


def test_an_engine_without_launch_is_served_whole(engine):
    """``ShardedEngine`` has ``execute`` alone: its batches run one
    after the other, each popped after the one before was scattered."""

    class Whole:
        def __init__(self, eng):
            self._eng, self.log = eng, []

        def __getattr__(self, name):
            if name in ("launch", "wait", "collect"):
                raise AttributeError(name)
            return getattr(self._eng, name)

        def execute(self, kind, sources, parts=None):
            self.log.append("execute")
            return self._eng.execute(kind, sources, parts)

    whole = Whole(engine)
    srv = engine.serve(ServeConfig(lane_widths=(1, 2, W), max_wait_s=60.0))
    srv.engine = whole
    futs = _submit(srv, 2 * W)
    done_at = []
    futs[W - 1].add_done_callback(lambda f: done_at.append(len(whole.log)))
    assert srv.pump() == 2
    _check(engine, futs)
    assert done_at == [1] and whole.log == ["execute"] * 2
    srv.close()


def test_bounded_pop_serves_the_kinds_in_turn():
    sch = Scheduler(ServeConfig(lane_widths=(W,), max_wait_s=60.0), N,
                    ("bfs", "sssp"))
    for i in range(3 * W):
        sch.submit("bfs", i)
    for i in range(W):
        sch.submit("sssp", i)
    kinds = [sch.pop_ready(max_batches=1)[0][0].kind for _ in range(4)]
    assert kinds == ["bfs", "sssp", "bfs", "bfs"]
    assert sch.pop_ready(max_batches=1) == []


@pytest.mark.parametrize("cell,devices", [
    ("g500-s20.bfs-sat", 1), ("g500-s22x4.bfs-sat", 4),
])
def test_rehearsed_cell_reads_the_hidden_tail(tmp_path, cell, devices):
    """``tests/chipbench/test_chipbench_parts_cell.py`` with the sign of
    ``batch_gap_ms`` turned (conftest.py says why that file stands as it
    is): through the real command at scale 9, the closed loop always
    has a lane due at the hand-off, so the median pop of a batch comes
    before its predecessor's scatter has ended."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "chipbench"))
    try:
        from rehearse import check_line, run_cell, small_benchmark
    finally:
        sys.path.pop(0)
    r, line = run_cell(small_benchmark(str(tmp_path)), cell, trace=1,
                       devices=devices)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    for part in ("launch_ms", "readback_ms", "to_global_ms"):
        assert 0 <= m[part] < m["execute_ms"]
    assert m["batch_gap_ms"] < 0
    assert m["readback_mb_per_query"] >= 2 * 512 * 16 * 4 / 16 / 1e6
    assert m["readback_mb_per_query"] < 2 * 512 * 16 * 4 / 1e6
    assert m["scatter_copied_mb"] == pytest.approx(
        2 * 512 * 4 * 16 / 1e6, rel=0.5)
    assert m["compiles_in_window"] == 0
    # the boot under the same run (PR 34): all six set-up metrics, and
    # what lies outside the program's top-level spans plus their union
    # (recomputed from the logged timeline) is the run's ``setup_s``
    boot = {"graph_ready_s", "upload_s", "boot_trace_s", "boot_fetch_s",
            "boot_probe_s", "boot_unspanned_s"}
    assert boot <= set(m) and all(m[k] >= 0 for k in boot)
    spans = [ln.split() for ln in r.stderr.splitlines() if " boot span " in ln]
    at = lambda f, key: float(f[f.index(key) + 1])
    # (a fresh directory: this run built its graph; only an engine that
    # kept its edge list has a companion to look after at warm-up)
    others = [f[4] for f in spans if f[4] != "serve.warmup"]
    assert others[:3] == [
        "serve.load", "serve.engine.init", "serve.server.init"]
    assert others[3:] in ([], ["serve.warmup.companion"])
    cover, reach = 0.0, 0.0
    for a, w in sorted((at(f, "at"), at(f, "wall")) for f in spans):
        cover += max(a + w - max(a, reach), 0.0)
        reach = max(reach, a + w)
    setup_s = float(r.stderr.rsplit("] setup_s ", 1)[1].split()[0])
    assert cover + m["boot_unspanned_s"] == pytest.approx(setup_s, abs=0.05)
    assert m["upload_s"] < m["graph_ready_s"] <= m["load_s"]
    plans = [ln for ln in r.stderr.splitlines() if " boot plan bfs w" in ln]
    assert len(plans) == len(spans) - len(others)
    # a plan's line adds up to its wall, and the plans to the warm-up
    total = 0.0
    for ln in plans:
        nums = ln.split(": ", 1)[1].replace(",", "").replace("(", "").split()
        parts = dict(zip(nums[0::2], map(float, nums[1::2])))
        assert sum(v for k, v in parts.items() if k != "wall") == \
            pytest.approx(parts["wall"], abs=0.01)
        total += parts["wall"]
    assert total == pytest.approx(m["warmup_s"], rel=0.05)
