"""``chipbench/boot.py`` and the six set-up readers over it: on a span
log written by hand (one case a reader), on the logs a built and a
restored boot of a tiny graph leave, and on a program that records no
``t0`` (the parent of the PR that added it)."""

import os
import sys
import time

import numpy as np
import pytest

from chipbench import boot
from chipbench.spec import CHECKOUT, Spec
from combblas_tpu import obs

METRICS = ["graph_ready_s", "upload_s", "boot_trace_s", "boot_fetch_s",
           "boot_probe_s", "boot_unspanned_s"]
T0 = 1000.0  # the process's start on the monotonic clock


def _span(path, t0, wall, **more):
    return dict(name=path.rpartition("/")[2], path=path, ts=5e8 + t0,
                t0=T0 + t0, wall_s=wall, **more)


def _ev(name, t, s):
    return {"name": name, "t": T0 + t, "s": s, "t_s": 0.0}


def _log():
    """A snapshot boot of one plan: restore 13-19, init, server, the
    companion, one plan 20-27 whose last second is the probe, the first
    send at 28; a batch's span inside the window."""
    return [
        _span("serve.restore/read", 13.0, 4.0),
        _span("serve.restore/upload", 17.0, 1.5),
        _span("serve.restore/companion", 18.5, 0.25),
        _span("serve.restore", 13.0, 6.0, attrs={"file_bytes": 10}),
        _span("serve.engine.init", 19.0, 0.125),
        _span("serve.server.init", 19.25, 0.25),
        _span("serve.warmup.companion", 19.5, 0.5),
        _span("serve.warmup/obs.opnames.publish", 26.0, 0.9,
              events=[_ev("lower", 26.4, 0.3), _ev("fetch", 26.8, 0.2)]),
        _span("serve.warmup", 20.0, 7.0,
              attrs={"kind": "bfs", "width": 16},
              parts=[{"stage": "build", "s": 0.5},
                     {"stage": "execute", "s": 5.5},
                     {"stage": "probe", "s": 1.0}],
              events=[
                  # an inner jit's trace inside the plan's own
                  _ev("trace", 21.0, 0.25), _ev("trace", 22.0, 1.5),
                  _ev("lower", 23.0, 1.0),
                  # a cache hit: the fetch inside JAX's compile interval
                  _ev("fetch", 24.4, 1.25), _ev("compile", 24.5, 1.5),
              ]),
        _span("serve.batch", 28.5, 1.0,
              events=[_ev("compile", 29.0, 0.5)]),
    ]


def _ctx(setup_s=28.0):
    return {"values": {"setup_s": setup_s}}


@pytest.fixture
def synthetic(monkeypatch):
    monkeypatch.setattr(boot, "process_start", lambda: T0)
    monkeypatch.setattr(boot, "span_log", lambda: (
        _log(), [_ev("trace", 12.5, 0.5), _ev("compile", 40.0, 3.0),
                 {"name": "frontier", "ts": 1.0, "t": T0 + 5.0}]))


@pytest.mark.parametrize("metric,want", [
    ("graph_ready_s", 6.0 + 0.125),
    ("upload_s", 1.5 + 0.25),
    # the plan's trace (its inner jit's inside it) and lower, and the
    # span-less trace; the probe's lower apart
    ("boot_trace_s", 1.5 + 1.0 + 0.5),
    # the compile interval holds its fetch; the window's compile and the
    # probe's fetch are not the boot's
    ("boot_fetch_s", 1.5),
    ("boot_probe_s", 1.0),
    # 28 - (restore 6 + init .125 + server .25 + companion .5 + plan 7)
    ("boot_unspanned_s", 28.0 - 13.875),
])
def test_a_reader_on_a_synthetic_boot(synthetic, metric, want):
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    assert spec.load_module("layers", metric).read(_ctx()) == \
        pytest.approx(want)


def test_the_timeline_of_a_synthetic_boot(synthetic, capsys):
    ctx = _ctx()
    tl = boot.boot(ctx)
    assert boot.boot(ctx) is tl  # computed and logged once a run
    assert [s["name"] for s in tl["top"]] == [
        "serve.restore", "serve.engine.init", "serve.server.init",
        "serve.warmup.companion", "serve.warmup"]
    assert tl["children"] == {"read": 4.0, "upload": 1.5, "companion": 0.25}
    plan, = tl["plans"]
    assert (plan["build"], plan["execute"], plan["probe"]) == (0.5, 5.5, 1.0)
    got = {k: plan[k] for k in ("trace", "lower", "fetch", "compile",
                                "first_run")}
    assert got == pytest.approx({"trace": 1.5, "lower": 1.0, "fetch": 1.25,
                                 "compile": 0.25, "first_run": 1.5})
    # the plan's line adds up to its wall
    assert sum(got.values()) + 0.5 + 1.0 == pytest.approx(plan["wall_s"])
    assert tl["spanned_s"] + tl["boot_unspanned_s"] == pytest.approx(28.0)
    err = capsys.readouterr().err
    assert err.count("boot span ") == 5 and err.count("boot plan ") == 1
    assert "boot serve.restore: companion 0.250 s, read 4.000 s" in err


def test_a_program_without_the_clock_reads_nothing(monkeypatch):
    monkeypatch.setattr(boot, "process_start", lambda: T0)
    old = [{k: v for k, v in s.items() if k != "t0"} for s in _log()]
    monkeypatch.setattr(boot, "span_log", lambda: (old, []))
    assert [boot.read(_ctx(), m) for m in METRICS] == [None] * 6
    # nor one with no reader of its span log, nor a caller without the
    # process's start or the run's setup_s
    monkeypatch.undo()
    monkeypatch.setattr(boot, "process_start", lambda: T0)
    import combblas_tpu.obs.spans  # noqa: F401 (the parent's obs.spans)

    monkeypatch.setattr(obs, "spans", sys.modules["combblas_tpu.obs.spans"])
    assert boot.span_log() is None
    assert boot.read(_ctx(), "boot_probe_s") is None
    monkeypatch.setattr(boot, "span_log", lambda: (_log(), []))
    assert boot.read({}, "boot_probe_s") is None
    monkeypatch.setattr(boot, "process_start", lambda: None)
    assert boot.read(_ctx(), "boot_probe_s") is None


def test_union_counts_nested_and_overlapping_seconds_once():
    assert boot.union_s([]) == 0.0
    assert boot.union_s([(0, 4), (1, 2), (3, 6), (8, 9)]) == 7.0
    assert boot.union_s([(0, 4), (3, 6)], lo=1, hi=5) == 4.0


@pytest.mark.parametrize("how", ["built", "restored"])
def test_a_real_boot_reads_a_number_everywhere(how, tmp_path, monkeypatch):
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import GraphEngine, ServeConfig
    from combblas_tpu.utils import checkpoint

    rng = np.random.default_rng(5)
    r, c = rng.integers(0, 64, 300), rng.integers(0, 64, 300)
    rows, cols = np.concatenate([r, c]), np.concatenate([c, r])
    grid = Grid.make(1, 1)
    eng = GraphEngine.from_coo(grid, rows, cols, 64, kinds=("bfs",))
    path = str(tmp_path / "v.npz")
    checkpoint.save_version(path, eng.version)
    obs.reset()
    obs.enable()
    try:
        t_start = time.perf_counter()
        monkeypatch.setattr(boot, "process_start", lambda: t_start)
        if how == "built":
            eng = GraphEngine.from_coo(grid, rows, cols, 64, kinds=("bfs",))
        else:
            eng = GraphEngine(grid, version=checkpoint.load_version(
                path, grid), kinds=("bfs",))
        srv = eng.serve(ServeConfig(lane_widths=(1,)))
        srv.warmup(kinds=("bfs",))
        ctx = _ctx(time.perf_counter() - t_start)
        got = {m: boot.read(ctx, m) for m in METRICS}
        srv.close()
    finally:
        obs.disable()
        obs.reset()
    assert None not in got.values(), got
    assert all(v >= 0 for v in got.values()), got
    tl = ctx["_boot"]
    assert tl["made"]["name"] == {"built": "serve.load",
                                  "restored": "serve.restore"}[how]
    assert got["upload_s"] <= got["graph_ready_s"]
    plan, = tl["plans"]
    assert plan["trace"] > 0 and plan["lower"] > 0 and plan["first_run"] > 0
