"""The deep-graph cell's part of the benchmark without the chip (PR 52):
the random geometric generator against its O(n^2) twin, the cost and the
five ``deep_*`` readers on hand-made records, what the cell added to
``BENCHMARK.json`` (by name and order, no place pinned), and one
rehearsal of ``rgg-n20.bfs-deep-sat`` through the real command at
n = 2^10 with one lane a batch, so that every wave's levels are one
root's depth + 1."""

import json
import os

import numpy as np
import pytest

from chipbench import deepcost, deepscopes, deepwork, graph, rgggraph
from chipbench.spec import CHECKOUT, Spec

from rehearse import check_line, run_cell, small_benchmark

CELL, CONFIG, MIX = "rgg-n20.bfs-deep-sat", "rgg-n20-1x1", "bfs-deep-sat"
READERS = ["deep_levels", "deep_push_share", "deep_level_us",
           "deep_ns_per_edge", "deep_hbm_share"]
#: the readers that were there and whose lists the cell joined, in the
#: file's order
JOINED = ["sat_lane_fill", "execute_ms", "scatter_ms", "bfs_device_ms",
          "hbm_peak_gb", "launch_ms", "readback_ms", "to_global_ms",
          "readback_mb_per_query", "scatter_copied_mb", "batch_gap_ms",
          "bfs_level_ms"]


def _spec():
    return Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))


# --- the generator -----------------------------------------------------------


def test_the_cell_search_finds_what_every_pair_s_distance_finds():
    n, rows, cols, keys = rgggraph.rgg_graph(10, 1)
    bn, brows, bcols, bkeys = rgggraph.brute_force(10, 1)
    assert n == bn == 1024
    np.testing.assert_array_equal(keys, bkeys)
    np.testing.assert_array_equal(rows, brows)
    np.testing.assert_array_equal(cols, bcols)
    # symmetrised, no loops, no duplicates, sorted by row then column
    assert rows.dtype == cols.dtype == np.int32
    assert np.all(np.diff(keys) > 0) and np.all(rows != cols)
    assert np.array_equal(np.sort(cols.astype(np.int64) * n + rows), keys)
    # the law: every edge under the radius, the degree near 0.3025 pi ln n
    pts, side = rgggraph.points(10, 1)
    d = pts[rows] - pts[cols]
    assert np.all((d * d).sum(axis=1) < rgggraph.radius(n) ** 2)
    assert 1.0 / side >= rgggraph.radius(n)
    assert abs(len(rows) / n - 0.3025 * np.pi * np.log(n)) < 1.0


def test_the_graph_is_a_function_of_the_seed():
    a, b, c = (rgggraph.rgg_graph(9, s) for s in (1, 1, 2))
    np.testing.assert_array_equal(a[3], b[3])
    assert len(a[3]) != len(c[3]) or np.any(a[3] != c[3])
    # the ids follow the cells: an edge joins neighbouring cells' ids
    pts, side = rgggraph.points(9, 1)
    cells = rgggraph._cell_of(pts, side)
    assert np.all(np.diff(cells) >= 0)


def test_the_reference_reads_the_graph_as_it_is_made():
    """``graph.Reference`` is law-blind: the generator's COO is what it
    expects (sorted by row), and a search over it is hundreds of levels
    deep where an R-MAT search of the size is six."""
    n, rows, cols, keys = rgggraph.rgg_graph(12, 1)
    ref = graph.Reference(n, rows, cols, keys)
    root = int(graph.draw_roots(ref.deg, 1, 1)[0])
    levels = ref.bfs_levels(root)
    assert levels.max() > 40
    parents = np.full(n, -1, np.int64)
    parents[root] = root
    for v in np.flatnonzero(levels > 0):
        nb = cols[rows == v]
        parents[v] = nb[levels[nb] == levels[v] - 1].max()
    assert ref.check_tree(levels, parents, root) is None
    assert ref.check_exact(levels, root) is None


# --- the cost and the readers -----------------------------------------------


def test_least_bytes_is_an_edge_s_id_and_a_vertex_s_two_words():
    assert deepcost.bfs_search_least_bytes(13_792_220, 1_048_571) == (
        4 * 13_792_220 + 8 * 1_048_571)


def _record(execute_s, width, **labels):
    return {"labels": dict(labels, status="ok", width=width),
            "stages": [{"stage": "execute", "s": execute_s}]}


def test_readers_read_the_program_s_own_count():
    wave = dict(levels=700, push_levels=690, push_edges=10 ** 8)
    ctx = {"stages": (
        [_record(5.0, 16, **wave)] * 16 + [_record(5.5, 16, **dict(
            wave, levels=800, push_levels=800))] * 14
        + [_record(0.1, 1, levels=2, push_levels=2, push_edges=3)]),
        "deep": {"edges_per_query": 1e7, "vertices_per_query": 1e6},
        "device": {"kind": "TPU v5 lite"}}
    assert deepwork.levels(ctx) == 750.0
    assert deepwork.push_share(ctx) == pytest.approx(
        100 * (690 + 800 + 2) / (700 + 800 + 2))
    # no trace: the device readers find nothing, and say so
    assert deepwork.level_us(ctx) is None
    assert deepwork.ns_per_edge(ctx) is None
    assert deepwork.hbm_share(ctx) is None
    # a wave of 15 queries on average, 5 s of device: by hand
    ctx["trace"] = {"devices": {"d": {"modules": {
        "jit_serve_bfs_w16": (2, 10.0)}}}}
    assert deepwork.ns_per_edge(ctx) == pytest.approx(5e9 / (15 * 1e7))
    assert deepwork.hbm_share(ctx) == pytest.approx(
        100 * (15 * (4e7 + 8e6) / 819e9) / 5.0)


def test_a_program_without_the_counts_reads_nothing():
    """The parent's stage records carry no ``levels``: every reader
    returns None and the result line leaves the five out."""
    ctx = {"stages": [_record(1.0, 16, slots=5, slots_skipped=0)] * 16,
           "deep": {"edges_per_query": 1e7, "vertices_per_query": 1e6}}
    for read in (deepwork.levels, deepwork.push_share, deepwork.level_us,
                 deepwork.ns_per_edge, deepwork.hbm_share):
        assert read(ctx) is None
    spec = _spec()
    for name in READERS:
        assert spec.load_module("layers", name).read(ctx) is None


def test_the_walk_s_scopes_are_told_apart_on_a_recorded_trace():
    """``deepscopes`` lays ``tests/chipbench/data/tiny_scoped.xplane.pb``
    (two whole executions of 10 and 8 us) under a table whose gather is
    a walked level's: the walk's scopes are kept on the label where
    ``scopes.label`` drops them, and every instant is charged once."""
    import tiny_scoped_trace as T

    here = os.path.dirname(os.path.abspath(__file__))
    walk = ("jit(serve_bfs_w16)/bfs.level/while/body/cond/branch_1_fun/"
            "bfs.push/while/body/push.walk/gather")
    table = dict(T.TABLE, **{"fusion.1": walk})
    assert deepscopes.label(walk) == "bfs.level/bfs.push/push.walk"
    assert deepscopes.label(T.TABLE["fusion.2"]) == (
        "bfs.level/ell.bucket0/fold")
    assert deepscopes.label(None) == "<none>"
    got = deepscopes.by_walk_scope(
        os.path.join(here, "data", "tiny_scoped.xplane.pb"),
        {T.MODULE: table})
    assert sum(got.values()) == pytest.approx(9e-6)
    # the five levels' gathers: 1990 + 2990 + 990 and run 2's, a run
    plain = deepscopes.by_walk_scope(
        os.path.join(here, "data", "tiny_scoped.xplane.pb"),
        {T.MODULE: T.TABLE})
    assert got["bfs.level/bfs.push/push.walk"] == pytest.approx(
        plain["bfs.level/ell.bucket0/gather"])
    assert got["bfs.level/bfs.push/push.walk"] > 2.9e-6
    assert deepscopes.by_walk_scope(
        os.path.join(here, "data", "tiny_scoped.xplane.pb"), {}) is None


# --- BENCHMARK.json ----------------------------------------------------------


def test_the_cell_its_configuration_and_its_five_readers_are_appended():
    """By name and order only: whatever a later PR appends, these hold."""
    spec = _spec()
    cells = [w["name"] for w in spec.doc["workloads"]]
    configs = [c["name"] for c in spec.doc["configs"]]
    assert cells.index("g500-sq15x4.spgemm-mesh") < cells.index(CELL)
    assert configs.index("g500-sq15-2x2") < configs.index(CONFIG)
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    reported = {m["name"] for m in spec.metrics_for(CELL, "end_to_end")}
    assert reported == {"qps", "setup_s"}
    qps = next(m for m in spec.doc["end_to_end"] if m["name"] == "qps")
    at = qps["workloads"].index
    assert at("g500-s20.bfs-sat") < at("g500-s20bc.bc-sat") < at(CELL)
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert [n for n in names if n in READERS] == READERS
    assert names.index("cc_ns_per_index") < names.index(READERS[0])
    by = {m["name"]: m for m in spec.doc["per_layer"]}
    assert [(by[n]["unit"], by[n]["better"], by[n]["source"])
            for n in READERS] == [
        ("levels", "lower", "program_counter"),
        ("%", "higher", "program_counter"),
        ("us", "lower", "device_trace"), ("ns", "lower", "device_trace"),
        ("%", "higher", "device_trace")]
    for n in READERS:
        assert by[n]["workloads"] == [CELL] and by[n]["moves"] == "qps"
        assert by[n]["layer"] == "algorithms + local kernels"
    # the lists it joined, after the cells that were there
    listed = [m["name"] for m in spec.doc["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in READERS]
    assert listed == JOINED
    for n in JOINED:
        assert by[n]["workloads"].index("g500-s20.bfs-sat") < (
            by[n]["workloads"].index(CELL))
    # a cell whose every level is walked sweeps nothing: the sweep's own
    # readers have nothing to read here, and their lists were not joined
    for n in ("ell_mslots_per_batch", "ell_skipped_share",
              "ell_ns_per_index", "bfs_gather_share"):
        assert CELL not in by[n]["workloads"]
    # and no other cell reports the five
    for other in cells:
        if other != CELL:
            assert not set(READERS) & {m["name"] for m in spec.metrics_for(
                other, "per_layer")}


def test_the_configuration_states_its_law_its_cut_and_its_guarantees():
    spec = _spec()
    cfg = spec.config(CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["scale"] == list(cfg["reduced"])
    assert (cfg["law"], cfg["radius"], cfg["graph_seed"], cfg["grid"]) == (
        "rgg", "0.55*sqrt(ln n / n)", 1, [1, 1])
    assert cfg["architecture"] is None
    assert cfg["kinds"] == ["bfs"] and cfg["keep_coo"] is True
    assert cfg["lane_widths"] == [1, 4, 16]
    assert rgggraph.RADIUS_FACTOR == 0.55
    assert {"levels", "tree", "batches", "unreachable"} == set(
        cfg["guarantees"])
    assert {"graph", "ids"} <= set(cfg["assumed"])
    mix = spec.traffic(MIX)
    base = spec.traffic("bfs-sat")
    assert mix["driver"] == "serve_closed_deep"
    assert {k: mix[k] for k in mix if k not in ("driver", "name")} == {
        k: base[k] for k in base if k not in ("driver", "name")}


# --- the rehearsal -----------------------------------------------------------


def _deep_benchmark(root, n_log2=10):
    """``small_benchmark`` with this cell's configuration cut to
    ``2**n_log2`` vertices and ONE lane a batch (one request in flight):
    a wave is one root's search."""
    bench = small_benchmark(str(root))
    path = os.path.join(str(root), "chipbench", "configs", CONFIG + ".json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(n_log2=n_log2, lane_widths=[1])
    with open(path, "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(CHECKOUT, "chipbench", "traffic",
                           MIX + ".json")) as f:
        mix = json.load(f)
    mix.update(in_flight=1, trace={"start_s": 0.5, "seconds": 1.0})
    with open(os.path.join(str(root), "chipbench", "traffic",
                           MIX + ".json"), "w") as f:
        json.dump(mix, f)
    return bench


def test_the_cell_rehearses_end_to_end(tmp_path):
    bench = _deep_benchmark(tmp_path)
    r, line = run_cell(bench, CELL, trace=0, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    check_line(line)
    assert set(line["metrics"]) == {"rehearsal.qps", "rehearsal.setup_s"}
    assert "levels deep" in r.stderr

    r, line = run_cell(bench, CELL, trace=1, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    check_line(line)
    got = {k.removeprefix("rehearsal."): v["value"]
           for k, v in line["metrics"].items()}
    # the program's counts; the device readers find no device plane here
    assert got["deep_push_share"] == 100.0
    assert not {"deep_level_us", "deep_ns_per_edge",
                "deep_hbm_share"} & set(got)
    assert not any(k.startswith("ell_") for k in got)
    assert {"sat_lane_fill", "execute_ms", "scatter_ms", "launch_ms",
            "readback_ms", "to_global_ms", "readback_mb_per_query",
            "scatter_copied_mb", "batch_gap_ms",
            "compiles_in_window"} <= set(got)
    assert got["compiles_in_window"] == 0.0
    # one lane a wave: a wave's levels are its root's depth + 1
    n, rows, cols, keys = rgggraph.rgg_graph(10, 1)
    ref = graph.Reference(n, rows, cols, keys)
    roots = graph.draw_roots(ref.deg, 2300001111, 4096)[:line["attempted"]]
    depths = [int(ref.bfs_levels(int(x)).max()) + 1 for x in roots]
    assert got["deep_levels"] == pytest.approx(np.mean(depths))
