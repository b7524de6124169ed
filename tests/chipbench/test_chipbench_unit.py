"""chipbench's own arithmetic, without running a cell: the schedule, the
wave rate, the validator, the trace reduction on the small recorded
trace, the table of peaks, and BENCHMARK.json against its contract."""

import json
import os
import re

import numpy as np
import pytest

from chipbench import cost, devtrace, graph, loadgen
from chipbench.spec import CHECKOUT, Spec

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny.xplane.pb")
NS = 1e-9


# -- load arithmetic ---------------------------------------------------------


def test_schedule_is_deterministic_in_the_seed():
    a = loadgen.poisson_offsets(7, 4.8, 45.0)
    b = loadgen.poisson_offsets(7, 4.8, 45.0)
    c = loadgen.poisson_offsets(8, 4.8, 45.0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == int(4.8 * 45.0)  # a fixed amount of work
    assert np.all(np.diff(a) > 0)
    assert abs(len(a) / a[-1] - 4.8) < 1.0  # about the asked rate


def test_latency_counts_from_the_scheduled_send():
    # a request due at 1.0 s, sent late at 1.4 s, done at 2.0 s, waited
    # 1.0 s as far as its user is concerned — not 0.6 s
    lat = loadgen.latencies(100.0, [1.0, 3.0], [102.0, np.nan])
    assert lat[0] == pytest.approx(1.0)
    assert np.isnan(lat[1])  # never completed: it misses every latency


def test_blocked_tail_does_not_move_with_one_stall():
    # 180 latencies of about 3.4 s in schedule order; a 6 s stall of the
    # generator delays 24 consecutive requests and the backlog it leaves
    rng = np.random.default_rng(5)
    clean = 3.4 + 0.6 * rng.random(180)
    stalled = clean.copy()
    stalled[40:70] += np.linspace(6.0, 0.0, 30)
    pooled = lambda v: float(np.percentile(v, 95))
    assert pooled(stalled) > 1.5 * pooled(clean)
    a = loadgen.blocked_percentile(clean, 95, 5)
    b = loadgen.blocked_percentile(stalled, 95, 5)
    assert b == pytest.approx(a, rel=0.02)
    assert a == pytest.approx(pooled(clean), rel=0.02)
    # one block is the plain percentile; blocks never outnumber values
    assert loadgen.blocked_percentile(clean, 95, 1) == pooled(clean)
    assert loadgen.blocked_percentile([1.0, 2.0], 50, 5) == 1.5


def test_wave_rate_is_not_quantised_by_the_window():
    # waves of 16 every 2.5 s, each settled over 150 ms
    times = np.concatenate(
        [w * 2.5 + np.linspace(0.0, 0.15, 16) for w in range(1, 6)]
    )
    w = loadgen.waves(times)
    assert [c for _, c in w] == [16] * 5
    assert loadgen.wave_rate(times) == pytest.approx(16 / 2.5)
    # the window ending inside the last wave's scatter changes nothing:
    # a wave counts when it ENDS inside the window
    assert loadgen.wave_rate(times, until=12.55) == pytest.approx(16 / 2.5)
    assert loadgen.wave_rate(times, until=12.4) == pytest.approx(16 / 2.5)
    # a system that settles continuously: the plain rate
    smooth = np.arange(100) * 0.1
    assert loadgen.wave_rate(smooth) == pytest.approx(10.0)
    assert loadgen.wave_rate([1.0]) is None


# -- generator and validator -------------------------------------------------


@pytest.fixture(scope="module")
def small():
    n, rows, cols, keys = graph.rmat_graph(9, 16, 1)
    ref = graph.Reference(n, rows, cols, keys)
    root = int(graph.draw_roots(ref.deg, 3, 1)[0])
    levels = ref.bfs_levels(root)
    parents = np.full(n, -1, np.int64)
    up = (levels[cols] == levels[rows] - 1) & (levels[rows] > 0)
    parents[rows[up]] = cols[up]
    parents[root] = root
    return ref, root, levels, parents


def test_generator_is_seeded_symmetric_and_clean():
    n, rows, cols, keys = graph.rmat_graph(9, 16, 1)
    n2, rows2, _, _ = graph.rmat_graph(9, 16, 1)
    assert n == 512 and np.array_equal(rows, rows2)
    assert not np.array_equal(rows, graph.rmat_graph(9, 16, 2)[1])
    assert np.all(rows != cols)  # de-looped
    assert np.all(np.diff(keys) > 0)  # deduplicated, sorted
    back = np.sort(cols.astype(np.int64) * n + rows)
    assert np.array_equal(back, keys)  # symmetric
    roots = graph.draw_roots(graph.degrees(rows, n), 5, 64)
    assert np.all(graph.degrees(rows, n)[roots] > 0)
    assert np.array_equal(roots, graph.draw_roots(graph.degrees(rows, n),
                                                  5, 64))


def test_validator_accepts_a_true_tree(small):
    ref, root, levels, parents = small
    assert ref.check_exact(levels, root) is None
    assert ref.check_tree(levels, parents, root) is None
    assert ref.traversed_edges(levels) == int(
        ref.deg[levels >= 0].sum()) // 2


def test_validator_refuses_a_corrupted_parent(small):
    ref, root, levels, parents = small
    v = int(np.flatnonzero(levels == 2)[0])
    bad = parents.copy()
    bad[v] = root  # a level-0 parent for a level-2 vertex
    assert "level[parent" in ref.check_tree(levels, bad, root)
    bad = parents.copy()
    # a parent one level up that is no neighbour
    nbrs = set(ref.cols[ref.rows == v].tolist())
    other = next(int(u) for u in np.flatnonzero(levels == 1)
                 if int(u) not in nbrs)
    bad[v] = other
    assert "is not an edge" in ref.check_tree(levels, bad, root)


def test_validator_refuses_a_corrupted_level(small):
    ref, root, levels, parents = small
    v = int(np.flatnonzero(levels == 1)[0])
    bad = levels.copy()
    bad[v] = 3
    assert ref.check_exact(bad, root) is not None
    assert ref.check_tree(bad, parents, root) is not None
    gone = levels.copy()
    gone[v] = -1  # reached in truth, reported unreached
    assert ref.check_tree(gone, parents, root) is not None


# -- trace reduction on the small recorded trace -----------------------------


def test_recorded_trace_is_the_text_beside_it():
    from jax.profiler import ProfileData

    from tiny_trace import TEXT

    # (a protobuf map has no fixed order on the wire, so the two are
    # compared as read, not byte for byte)
    fresh = ProfileData.text_proto_to_serialized_xspace(TEXT)
    assert devtrace.reduce_xplane(fresh) == devtrace.reduce_xplane(TINY)


def test_trace_reduction_on_the_recorded_trace():
    r = devtrace.reduce_xplane(TINY)
    d0, d1 = r["devices"]["/device:TPU:0"], r["devices"]["/device:TPU:1"]
    # busy union: nested ops are not counted twice
    assert d0["busy_s"] == pytest.approx(17100 * NS)
    assert d1["busy_s"] == pytest.approx(7200 * NS)
    assert r["busy_s"] == pytest.approx(12150 * NS)  # mean of the devices
    assert r["window_s"] == pytest.approx(22900 * NS)
    idle_share = 1 - d0["busy_s"] / r["window_s"]
    assert idle_share == pytest.approx(5800 / 22900)
    # per-op totals are SELF times: the while is charged its own 3000 ns
    assert d0["ops"]["fusion.1"] == pytest.approx(9000 * NS)
    assert d0["ops"]["all-reduce.2"] == pytest.approx(4000 * NS)
    assert d0["ops"]["while.1"] == pytest.approx(3000 * NS)
    assert d0["ops"]["copy.3"] == pytest.approx(1100 * NS)
    assert sum(d0["ops"].values()) == pytest.approx(d0["busy_s"])
    # collective time
    assert d0["collective_s"] == pytest.approx(4000 * NS)
    assert d1["collective_s"] == pytest.approx(3000 * NS)
    # program executions: the one the trace's end cut is left out
    assert d0["modules"]["jit_impl"] == [2, pytest.approx(16000 * NS)]
    name, runs, per_run = devtrace.dominant_module(r)
    assert (name, runs) == ("jit_impl", 1)  # 3 executions over 2 devices
    assert per_run == pytest.approx(23000 * NS / 3)
    assert devtrace.top_ops(r)[0][0] == "fusion.1"
    assert r["anchor_s"] == pytest.approx(500 * NS)


def test_trace_slice_from_the_anchor_and_idle_gap_attribution():
    r = devtrace.reduce_xplane(TINY, slice_s=20000 * NS)
    assert r["window"] == pytest.approx((500 * NS, 20500 * NS))
    assert r["devices"]["/device:TPU:0"]["busy_s"] == pytest.approx(
        16000 * NS)  # both copy.3 lie outside the slice
    r = devtrace.reduce_xplane(TINY)
    off = 100.0  # host clock = trace clock + 100 s
    spans = [("execute", off + 500 * NS, off + 10000 * NS),
             ("scatter", off + 10000 * NS, off + 12000 * NS),
             ("execute", off + 12000 * NS, off + 21000 * NS)]
    gaps = dict(devtrace.idle_gaps(r, spans, off))
    want = {"execute:after-device": 2000, "scatter:before-device": 2000,
            "no-host-span": 1300, "execute:before-device": 500}
    assert set(gaps) == set(want)
    for label, ns in want.items():
        assert gaps[label] == pytest.approx(ns * NS, rel=1e-3), label


def test_layer_readers_on_the_recorded_trace():
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    ctx = {"trace": devtrace.reduce_xplane(TINY),
           "device": {"kind": "TPU v5 lite"}, "least_bytes": 819}
    read = lambda name: spec.load_module("layers", name).read(ctx)
    assert read("device_skew") == pytest.approx(100 * 9900 / 17100)
    assert read("collective_share") == pytest.approx(
        100 * (4000 / 17100 + 3000 / 7200) / 2)
    assert read("bfs_device_ms") == pytest.approx(23000 * NS / 3 * 1e3)
    # 819 bytes at 819 GB/s is 1 ns of the program's 7666.7 ns
    assert read("hbm_share") == pytest.approx(100 * 3 / 23000)
    # nothing to read: nothing returned
    assert spec.load_module("layers", "queue_wait_ms").read({}) is None
    assert spec.load_module("layers", "device_skew").read({}) is None


# -- peaks and computed bytes ------------------------------------------------


def test_peaks_table_refuses_an_unknown_device():
    assert cost.peaks("TPU v5 lite")["hbm_gbps"] == 819.0
    with pytest.raises(KeyError, match="no peaks for device kind"):
        cost.peaks("cpu")


def test_least_bytes_of_a_batch():
    n, slots, w, levels = 1 << 20, 36_000_000, 256, 7
    want = levels * (4 * slots + 2 * n * w) + (4 * slots + 5 * n * w)
    assert cost.bfs_batch_least_bytes(n, slots, w, levels) == want


# -- BENCHMARK.json against its contract -------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_meets_the_contract():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        raw = f.read()
    doc = json.loads(raw)
    assert len(raw) <= 64 * 1024
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= doc["run_seconds"] <= 51
    # a full check with 24 cells fits 43200 s
    runs = 2 + 14 * 24
    assert (runs * (doc["run_seconds"] + 60) + 24 * 2 * 90 + 1200) <= 43200
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    configs = {c["name"] for c in doc["configs"]}
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        assert all(NAME.match(k) for k in c["reduced"])
        cfg = spec.config(c["name"])
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
    cells = doc["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(len(cells) // 2, 1)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        mix = spec.traffic(w["traffic"])
        spec.find(os.path.join("drivers", mix["driver"] + ".py"))
        mine = {m["name"] for m in spec.metrics_for(w["name"],
                                                     "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layer = [m for m in spec.metrics_for(w["name"], "per_layer")
                 if m["moves"] in mine]
        assert layer
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [w["name"] for w in cells]
    where = lambda m: set(m.get("workloads", names))
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
        # reported only where the metric it moves is (the driver refuses
        # the file otherwise: a metric with no list is in every cell)
        moved = next(e for e in doc["end_to_end"]
                     if e["name"] == m["moves"])
        assert where(m) <= where(moved), m["name"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert callable(spec.load_module("layers", m["name"]).read)
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for wl in m.get("workloads", []):
            assert wl in {w["name"] for w in cells}


def test_layers_named_in_the_benchmark_are_perf_md_layers():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(os.path.join(CHECKOUT, "PERF.md")) as f:
        perf = f.read()
    for m in doc["per_layer"]:
        assert m["layer"] in perf, m["layer"]


def test_importing_chipbench_loads_no_jax():
    import subprocess
    import sys

    code = ("import sys, chipbench, chipbench.run, chipbench.devtrace, "
            "chipbench.serving, chipbench.cost; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=CHECKOUT,
                   timeout=60)
