"""Round-15 production observability: per-request tracing, the flight
recorder, SLO error budgets, freshness gauges, label-space pruning and
the Prometheus export surface (ISSUE 13; docs/observability.md
"Serving observability")."""

import json
import os
import urllib.request

import numpy as np
import pytest

from combblas_tpu import obs
from combblas_tpu.obs import export as obs_export
from combblas_tpu.obs import trace as obs_trace
from combblas_tpu.obs.recorder import FlightRecorder
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import (
    ErrorBudget,
    GraphEngine,
    ServeConfig,
)


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    obs_trace.set_sample_rate(None)
    yield
    obs.disable()
    obs.reset()
    obs_trace.set_sample_rate(None)


N = 48


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    """One tiny BFS engine shared by the module (plan compiles paid
    once); tests build their own worker-less Servers over it."""
    rng = np.random.default_rng(3)
    r = rng.integers(0, N, 220)
    c = rng.integers(0, N, 220)
    return GraphEngine.from_coo(
        Grid.make(1, 1), np.concatenate([r, c]), np.concatenate([c, r]),
        N, kinds=("bfs",), keep_coo=True,
    )


def _cfg(tmp_path, **kw):
    kw.setdefault("lane_widths", (1, 2))
    kw.setdefault("update_autostart", False)
    kw.setdefault("flight_recorder_dir", str(tmp_path))
    return ServeConfig(**kw)


# --- deterministic sampling -------------------------------------------------


def test_sampling_deterministic_and_proportional():
    ids = list(range(1000))
    a = {i for i in ids if obs_trace.sampled(i, 0.3)}
    b = {i for i in ids if obs_trace.sampled(i, 0.3)}
    assert a == b  # same ids + same rate = same sampled set
    assert 0.2 < len(a) / len(ids) < 0.4  # roughly the asked rate
    # rate monotonicity: raising the rate only ADDS ids
    c = {i for i in ids if obs_trace.sampled(i, 0.6)}
    assert a <= c
    assert {i for i in ids if obs_trace.sampled(i, 0.0)} == set()
    assert {i for i in ids if obs_trace.sampled(i, 1.0)} == set(ids)


def test_sample_rate_env_resolution(monkeypatch):
    from combblas_tpu.tuner import config as tuner_config

    monkeypatch.setenv(tuner_config.ENV_OBS_TRACE_SAMPLE, "0.25")
    obs_trace.set_sample_rate(None)  # re-resolve
    assert obs_trace.sample_rate() == 0.25
    assert tuner_config.obs_trace_sample(2.0) == 1.0  # clamped


# --- the pump stage-sum contract --------------------------------------------


def test_pump_trace_stages_sum_to_e2e(engine, tmp_path):
    obs.enable(install_hooks=False)
    obs_trace.set_sample_rate(1.0)
    srv = engine.serve(_cfg(tmp_path))
    srv.warmup(widths=(1, 2))
    futs = [srv.submit("bfs", i) for i in (1, 2, 3)]
    while srv.pump(force=True):
        pass
    for f in futs:
        assert f.exception(timeout=0) is None
    srv.close()
    recs = [
        r for r in obs.trace_records() if r["name"] == "serve.request"
    ]
    assert len(recs) == 3
    for rec in recs:
        obs.validate_record({"v": 1, "kind": "trace", **rec})
        stages = [st["stage"] for st in rec["stages"]]
        assert stages[:3] == ["queue_wait", "assemble", "execute"]
        assert stages[-1] == "scatter"
        # THE acceptance property: stage durations telescope to the
        # end-to-end latency (each mark charges since the last one)
        total = sum(st["s"] for st in rec["stages"])
        assert abs(total - rec["wall_s"]) < 1e-6, rec
        assert rec["labels"]["status"] == "ok"
        assert rec["labels"]["kind"] == "bfs"
        assert rec["labels"]["plan"] in ("warm", "cold")
        assert rec["labels"]["width"] in (1, 2)
        assert rec["labels"]["version"] == engine.version_id


def test_write_lane_trace_stages(engine, tmp_path):
    obs.enable(install_hooks=False)
    obs_trace.set_sample_rate(1.0)
    srv = engine.serve(_cfg(tmp_path))
    fut = srv.submit_update([("insert", 0, 9), ("insert", 9, 0)])
    srv.pump_updates(force=True)
    assert fut.result(timeout=10)["ops"] == 2
    srv.close()
    recs = [
        r for r in obs.trace_records() if r["name"] == "serve.update"
    ]
    assert len(recs) == 1
    rec = recs[0]
    obs.validate_record({"v": 1, "kind": "trace", **rec})
    assert [st["stage"] for st in rec["stages"]] == [
        "buffer_wait", "merge", "swap", "settle",
    ]
    assert abs(
        sum(st["s"] for st in rec["stages"]) - rec["wall_s"]
    ) < 1e-6
    assert rec["labels"]["mode"] in ("incremental", "rebuild")


def test_trace_jsonl_roundtrip(engine, tmp_path):
    path = str(tmp_path / "trace.jsonl")
    obs.enable(jsonl_path=path, install_hooks=False)
    obs_trace.set_sample_rate(1.0)
    srv = engine.serve(_cfg(tmp_path))
    srv.submit("bfs", 1)
    while srv.pump(force=True):
        pass
    srv.close()
    obs.dump_jsonl()
    recs = obs.parse_jsonl(path)  # validates every line
    traces = [r for r in recs if r["kind"] == "trace"]
    assert traces and traces[0]["name"] == "serve.request"
    agg = obs.aggregate(recs)
    assert len(agg["traces"]) == len(traces)
    # expired requests close their trace with a timeout status
    assert obs.registry.get_counter(
        "serve.trace.sampled", lane="request"
    ) >= 1


# --- zero-cost-when-disabled gates ------------------------------------------


def test_round15_zero_cost_when_disabled(engine, tmp_path):
    """The round-15 analog of the existing gate tests: with obs off
    (and the recorder opted out) the serve path books NOTHING — no
    registry entries, no trace records, no recorder object."""
    assert not obs.ENABLED
    srv = engine.serve(_cfg(tmp_path, flight_recorder=False))
    assert srv._recorder is None  # one attribute read on the batch path
    assert srv.slo is None  # no SLO configured = no budget object
    f = srv.submit("bfs", 1)
    while srv.pump(force=True):
        pass
    assert f.exception(timeout=0) is None
    srv.close()
    assert obs.registry.empty()
    assert obs.trace_records() == []
    # obs ON but sampling at 0 (the default): still no traces
    obs.enable(install_hooks=False)
    obs_trace.set_sample_rate(0.0)
    srv = engine.serve(_cfg(tmp_path, flight_recorder=False))
    f = srv.submit("bfs", 2)
    while srv.pump(force=True):
        pass
    assert f.exception(timeout=0) is None
    srv.close()
    assert obs.trace_records() == []


# --- flight recorder --------------------------------------------------------


def test_flight_recorder_ring_and_dump(tmp_path):
    rec = FlightRecorder(capacity=3, out_dir=str(tmp_path),
                         min_interval_s=0.0)
    for i in range(5):
        rec.record("ev", i=i, query="bfs")  # reserved-name remap
    snap = rec.snapshot()
    assert [e["i"] for e in snap] == [2, 3, 4]  # bounded, oldest first
    path = rec.dump("manual", query="bfs")
    recs = obs.parse_jsonl(path)  # both schemas validate
    assert recs[0]["schema"] == obs.FLIGHTREC_SCHEMA
    assert recs[0]["reason"] == "manual"
    assert [r["i"] for r in recs[1:]] == [2, 3, 4]
    # rate limit: an immediate second dump is suppressed
    rec.min_interval_s = 60.0
    assert rec.dump("manual") is None
    assert rec.dumps == 1


def test_injected_fault_dumps_poisoned_batch(engine, tmp_path):
    """Acceptance: an injected fault produces a flight-recorder dump
    containing the poisoned batch's stage events."""
    obs.enable(install_hooks=False)
    srv = engine.serve(_cfg(tmp_path))
    srv.warmup(widths=(1, 2))
    srv.faults.rate("engine.execute", 1.0, seed=5)
    f = srv.submit("bfs", 1)
    while srv.pump(force=True):
        pass
    assert f.exception(timeout=0) is not None
    dump = srv._recorder.last_dump
    assert dump is not None and os.path.dirname(dump) == str(tmp_path)
    recs = obs.parse_jsonl(dump)
    assert recs[0]["reason"] == "poisoned"
    assert recs[0]["query"] == "bfs"
    evs = [
        r for r in recs
        if r["kind"] == "event" and r["name"] == "serve.batch"
    ]
    assert evs, recs
    assert any(e.get("outcome") == "error" for e in evs)
    assert obs.registry.get_counter(
        "serve.flightrec.dumps", reason="poisoned"
    ) == 1
    assert srv.stats()["flightrec"]["dumps"] == 1
    assert srv.health()["flightrec_last_dump"] == dump
    srv.faults.clear()
    srv.close()


# --- SLO error budgets ------------------------------------------------------


def test_error_budget_window_and_breach():
    clock = [100.0]
    eb = ErrorBudget(target=0.9, window_s=10.0, tenant="t0",
                     clock=lambda: clock[0])
    for _ in range(9):
        assert eb.record(True) is False
    # 9 good + 1 bad: budget = 0.1 * 10 = 1.0, burn = 1.0 -> breach
    assert eb.record(False) is True  # the TRANSITION returns True
    assert eb.record(False) is False  # already breached: no re-fire
    d = eb.describe()
    assert d["breached"] and d["burn"] >= 1.0
    assert d["window_good"] == 9 and d["window_bad"] == 2
    # the window rolls: 11 s later the old buckets expire — and a
    # breached-then-IDLE budget must recover on read alone (no new
    # record()), or an idle tenant would page degraded forever
    clock[0] += 11.0
    d = eb.describe()
    assert d["window_bad"] == 0 and not d["breached"]
    for _ in range(20):
        eb.record(True)
    d = eb.describe()
    assert d["window_bad"] == 0 and not d["breached"]
    assert d["bad_total"] == 2  # lifetime totals survive the window


def test_server_slo_accounting_and_health(engine, tmp_path):
    obs.enable(install_hooks=False)
    srv = engine.serve(_cfg(
        tmp_path, slo_deadline_s=30.0, slo_target=0.5,
        slo_window_s=60.0,
    ))
    srv.warmup(widths=(1, 2))
    ok = [srv.submit("bfs", i) for i in (1, 2)]
    while srv.pump(force=True):
        pass
    for f in ok:
        assert f.exception(timeout=0) is None
    st = srv.stats()["slo"]
    assert st["window_good"] == 2 and st["window_bad"] == 0
    assert obs.registry.get_counter("serve.slo.good", kind="bfs") == 2
    # a poisoned request is a BAD disposition and burns the budget
    srv.faults.rate("engine.execute", 1.0, seed=5)
    bad = srv.submit("bfs", 3)
    while srv.pump(force=True):
        pass
    assert bad.exception(timeout=0) is not None
    srv.faults.clear()
    st = srv.stats()["slo"]
    assert st["window_bad"] == 1
    assert obs.registry.get_counter("serve.slo.bad", kind="bfs") == 1
    assert obs.registry.get_gauge("serve.slo.budget_burn") is not None
    h = srv.health()
    assert h["slo"]["window_bad"] == 1
    srv.close()


# --- freshness gauges -------------------------------------------------------


def test_freshness_gauges_on_refresh(tmp_path):
    obs.enable(install_hooks=False)
    rng = np.random.default_rng(9)
    r = rng.integers(0, 32, 140)
    c = rng.integers(0, 32, 140)
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), np.concatenate([r, c]),
        np.concatenate([c, r]), 32, kinds=("bfs",), keep_coo=True,
    )
    srv = eng.serve(_cfg(tmp_path))
    root = int(r[0])
    eng.refresh("bfs", root=root)  # cold: seeds the analytics cache
    # one merged write: the cached analytic is now one version behind
    fut = srv.submit_update([("insert", 0, 31), ("insert", 31, 0)])
    srv.pump_updates(force=True)
    assert fut.exception(timeout=10) is None
    out = eng.refresh("bfs", root=root)
    assert out["mode"] == "warm"  # insert-only lineage repairs
    assert obs.registry.get_gauge(
        "dynamic.freshness.versions_behind", kind="bfs"
    ) == 1
    ratio = obs.registry.get_gauge("dynamic.freshness.repair_ratio")
    assert ratio == 0.5  # 1 warm / (1 warm + 1 cold)
    fresh = eng.stats()["freshness"]
    assert fresh["refresh_modes"] == {"cold": 1, "warm": 1}
    assert fresh["repair_ratio"] == 0.5
    assert fresh["versions_behind"] == 0  # cache repaired to current
    srv.close()


# --- label-space pruning on tenant churn ------------------------------------


def test_pool_tenant_churn_prunes_label_space(tmp_path):
    """ISSUE 13 satellite regression: add/remove tenant cycles must
    return the registry's label count to baseline — a removed tenant's
    ``tenant=...`` series must not survive it."""
    from combblas_tpu.serve import EnginePool

    obs.enable(install_hooks=False)
    rng = np.random.default_rng(4)
    r = rng.integers(0, 32, 120)
    c = rng.integers(0, 32, 120)
    rows, cols = np.concatenate([r, c]), np.concatenate([c, r])
    grid = Grid.make(1, 1)
    cfg = ServeConfig(lane_widths=(1,), update_autostart=False,
                      flight_recorder=False)
    pool = EnginePool(grid)
    psrv = pool.serve()
    baseline = len(obs.metrics_snapshot())

    def tenant_series():
        return [
            rec for rec in obs.metrics_snapshot()
            if rec["labels"].get("tenant") == "x"
        ]

    sizes = []
    for _ in range(3):  # add/serve/remove cycles
        pool.add_tenant("x", rows, cols, 32, config=cfg, kinds=("bfs",))
        f = psrv.submit("x", "bfs", 1)
        while psrv.pump(force=True):
            pass
        assert f.exception(timeout=0) is None
        assert tenant_series()  # labeled series exist while serving
        pool.remove_tenant("x")
        assert tenant_series() == []  # ...and are pruned on removal
        sizes.append(len(obs.metrics_snapshot()))
    # unlabeled/global series have appeared with the first cycle (a
    # served batch's own: bytes, plan cache, the ELL family's one series
    # a degree class and mode), but nothing grows per departed tenant:
    # the tenant-labeled count is back to zero and the snapshot does not
    # accumulate cycle over cycle
    assert sizes[0] > baseline and sizes[1:] == sizes[:1] * 2, sizes
    # the WFQ-prune path also sweeps the registry: simulate a tenant
    # removed between pumps with stale labeled state
    obs.gauge("serve.wfq.deficit", 1.0, tenant="ghost")
    psrv.wfq.add("ghost", 1.0)
    psrv.pump(force=True)  # no backlog: returns 0, but prunes first
    assert [
        rec for rec in obs.metrics_snapshot()
        if rec["labels"].get("tenant") == "ghost"
    ] == []


# --- Prometheus export ------------------------------------------------------


def test_exposition_parity_with_registry():
    """Acceptance: the scrape endpoint's rendered text agrees with the
    registry snapshot (counter / gauge / quantile parity)."""
    obs.enable(install_hooks=False)
    obs.count("par.requests", 5, kind="bfs")
    obs.count("par.requests", 2, kind="pr")
    obs.gauge("par.depth", 7.5)
    for v in (0.1, 0.2, 0.3, 0.4, 1.0):
        obs.observe("par.lat", v, kind="bfs")
    snap = obs.metrics_snapshot()
    text = obs_export.render(snap)
    parsed = obs_export.parse_exposition(text)
    for rec in snap:
        name = obs_export.metric_name(rec["name"])
        if rec["kind"] in ("counter", "gauge"):
            key = (name, obs_export._labels(rec["labels"]))
            assert parsed[key] == pytest.approx(rec["value"])
        else:
            lab = rec["labels"]
            assert parsed[
                (f"{name}_count", obs_export._labels(lab))
            ] == rec["count"]
            assert parsed[
                (f"{name}_sum", obs_export._labels(lab))
            ] == pytest.approx(rec["sum"])
            for q, fld in (("0.50", "p50"), ("0.95", "p95"),
                           ("0.99", "p99")):
                key = (name, obs_export._labels(lab, {"quantile": q}))
                assert parsed[key] == pytest.approx(rec[fld])
    # quantiles come from ONE shared implementation
    from combblas_tpu.obs.sinks import quantiles

    assert quantiles([0.1, 0.2, 0.3, 0.4, 1.0])[0.5] == pytest.approx(
        0.3
    )


def test_scrape_endpoint_live(engine, tmp_path):
    obs.enable(install_hooks=False)
    srv = engine.serve(_cfg(tmp_path))
    f = srv.submit("bfs", 1)
    while srv.pump(force=True):
        pass
    assert f.exception(timeout=0) is None
    port = srv.serve_metrics()
    assert port == srv.serve_metrics()  # idempotent
    base = f"http://127.0.0.1:{port}"
    text = urllib.request.urlopen(f"{base}/metrics", timeout=10
                                  ).read().decode()
    # the served text agrees with a fresh render of the registry
    assert obs_export.parse_exposition(text) == (
        obs_export.parse_exposition(obs_export.render())
    )
    assert "combblas_serve_requests" in text
    hz = json.loads(urllib.request.urlopen(
        f"{base}/healthz", timeout=10
    ).read())
    assert hz["status"] in ("ok", "degraded")
    sz = json.loads(urllib.request.urlopen(
        f"{base}/statz", timeout=10
    ).read())
    assert sz["completed"] >= 1
    assert obs.registry.get_counter(
        "obs.scrape.requests", path="/metrics"
    ) >= 1
    srv.close()  # stops the scrape thread
    assert srv._scrape is None


def test_procfleet_metrics_federation_parity():
    """ISSUE 16: one ``ProcessFleet`` ``/metrics`` scrape federates
    the router's registry with every replica's heartbeat-piggybacked
    child snapshot, relabeled ``replica=i``.  Parity-tested through
    the rendered exposition over stub replicas: the child snapshot is
    a GENUINE ``metrics_snapshot()`` wire shape (what ``_hb_loop``
    piggybacks), the subprocess itself is not needed to test the
    fold."""
    import types

    from combblas_tpu.serve.procfleet import ProcessFleet

    obs.enable(install_hooks=False)
    # forge the child's snapshot by actually populating a registry
    obs.count("serve.requests", 3, kind="bfs")
    for v in (0.01, 0.02):
        obs.observe("serve.e2e_s", v, kind="bfs")
    child_snap = obs.metrics_snapshot()
    obs.reset()
    obs.count("serve.requests", 2, kind="pr")  # router-side series
    stub = types.SimpleNamespace(replicas=[
        types.SimpleNamespace(last_metrics=child_snap,
                              last_metrics_t=1.0),
        types.SimpleNamespace(last_metrics=None,  # no heartbeat yet
                              last_metrics_t=0.0),
    ])
    # the fleet's REAL fold, bound to the stub — the scrape handler
    # discovers it by name on the owner
    stub.metrics_records = ProcessFleet.metrics_records.__get__(stub)
    recs = stub.metrics_records()
    # every child record is relabeled; the router's stay unlabeled
    assert {r["labels"].get("replica")
            for r in recs} == {None, 0}
    port = obs_export.attach_scrape(stub)
    assert port == obs_export.attach_scrape(stub)  # idempotent
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ).read().decode()
    parsed = obs_export.parse_exposition(text)
    # parity: the served text agrees with a fresh federated render
    assert parsed == obs_export.parse_exposition(
        obs_export.render(stub.metrics_records())
    )
    child_lab = obs_export._labels({"kind": "bfs", "replica": 0})
    assert parsed[("combblas_serve_requests", child_lab)] == 3
    assert parsed[
        ("combblas_serve_e2e_s_count", child_lab)
    ] == 2  # histograms federate with their quantile summaries
    assert parsed[
        ("combblas_serve_requests", obs_export._labels({"kind": "pr"}))
    ] == 2
    obs_export.detach_scrape(stub)
    assert stub._scrape is None


def test_export_cli_renders_jsonl(tmp_path, capsys):
    path = str(tmp_path / "t.jsonl")
    obs.enable(jsonl_path=path, install_hooks=False)
    obs.count("cli.hits", 3)
    obs.dump_jsonl()
    out = str(tmp_path / "m.prom")
    assert obs_export.main([path, "--out", out]) == 0
    text = open(out).read()
    assert ("combblas_cli_hits", "") in obs_export.parse_exposition(
        text
    )


# --- aggregate quantile summaries -------------------------------------------


def test_aggregate_merges_reservoir_quantiles(tmp_path):
    """Satellite: p50/p95/p99 computed once in ``aggregate()`` from
    the histogram reservoirs, across processes."""
    paths = []
    for proc, vals in enumerate(([0.1, 0.2], [0.3, 0.4])):
        obs.reset()
        obs.enable(install_hooks=False)
        for v in vals:
            obs.observe("agg.lat", v)
        p = str(tmp_path / f"p{proc}.jsonl")
        obs.dump_jsonl(p, process=proc, nprocs=2)
        paths.append(p)
    agg = obs.merge_jsonl_files(paths)
    h = agg["histograms"]["agg.lat"]
    assert h["count"] == 4
    assert h["p50"] == pytest.approx(0.25)
    assert h["p99"] == pytest.approx(0.397)


def test_scrape_attach_close_attach_cycle():
    """Round-20 bugfix: repeated serve_metrics()/close() cycles on one
    owner must attach a FRESH working server each time (the old code
    returned the stopped server's dead port), stop() is idempotent
    (a double shutdown() of ThreadingHTTPServer blocks forever), and
    concurrent attaches collapse to one server."""
    import threading
    import types

    obs.enable(install_hooks=False)
    obs.count("serve.requests", 1, kind="bfs")
    stub = types.SimpleNamespace()
    p1 = obs_export.attach_scrape(stub)
    s1 = stub._scrape
    obs_export.detach_scrape(stub)
    assert stub._scrape is None
    s1.stop()  # second stop: must return, not block
    # re-attach after close: a FRESH live server, not the dead one
    p2 = obs_export.attach_scrape(stub)
    assert stub._scrape is not s1 and not stub._scrape._stopped
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{p2}/metrics", timeout=10
    ).read().decode()
    assert "combblas_serve_requests" in text
    # an owner whose scrape was stopped WITHOUT detach (a close path
    # that bypassed detach_scrape) also re-attaches fresh
    stub._scrape.stop()
    p3 = obs_export.attach_scrape(stub)
    assert not stub._scrape._stopped
    # concurrent attaches: one server, one port
    obs_export.detach_scrape(stub)
    ports = []

    def attach():
        ports.append(obs_export.attach_scrape(stub))

    threads = [threading.Thread(target=attach) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(set(ports)) == 1
    obs_export.detach_scrape(stub)
    obs_export.detach_scrape(stub)  # idempotent no-op
    assert stub._scrape is None
