"""GAP's TC kernel's part of the benchmark without the chip: the plain
reference against the definition and against hand-made triples, the
driver's checks and its fast exit on a program without the entry, the
control's three altered counts, the cost function by hand, the six
readers on a small trace of a program with the harvest's scopes and
without them, what the cell added to ``BENCHMARK.json`` (order checks,
no place pinned), and one rehearsal of ``g500-s18tc.tc-batch`` through
the real command at scale 8."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import devtrace, graph, scopes, tccost, tcref, tcscopes
from chipbench.spec import CHECKOUT, Spec

from rehearse import check_line, run_cell, small_benchmark

NS = 1e-9
CELL, CONFIG, MIX = "g500-s18tc.tc-batch", "g500-s18-tc-1x1", "tc-batch"
CC_CELL, CC_CONFIG = "g500-s20cc.cc-batch", "g500-s20-cc-1x1"
READERS = ["tc_device_ms", "tc_pack_ms", "tc_harvest_ms",
           "tc_pairs_per_edge", "tc_hbm_share", "tc_hbm_peak_gb"]


def _spec():
    return Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))


# --- the reference ----------------------------------------------------------

#   0 - 1 - 2 - 0 (a triangle), 2 - 3, 3 - 4 - 5 - 3 (another), 6 alone
EDGES = [(0, 1), (1, 2), (2, 0), (2, 3), (3, 4), (4, 5), (5, 3)]


def _coo(edges):
    r = np.array([e[0] for e in edges] + [e[1] for e in edges], np.int32)
    c = np.array([e[1] for e in edges] + [e[0] for e in edges], np.int32)
    return r, c


def test_reference_counts_each_triangle_once():
    r, c = _coo(EDGES)
    ref = tcref.TCReference(7, r, c)
    assert (ref.triangles, ref.edges) == (2, 7)
    assert tcref.brute_force(7, r, c) == 2
    # loops and repeated entries change nothing, in any order
    r2 = np.concatenate([r, r[:5], [6, 3]])
    c2 = np.concatenate([c, c[:5], [6, 3]])
    again = tcref.TCReference(7, r2[::-1], c2[::-1])
    assert (again.triangles, again.edges) == (2, 7)
    # one direction of every edge is the same undirected graph
    half = tcref.TCReference(7, r[:7], c[:7])
    assert (half.triangles, half.edges) == (2, 7)
    # K5: ten triangles; the blocks of the product do not matter
    k5 = [(i, j) for i in range(5) for j in range(i)]
    assert tcref.TCReference(5, *_coo(k5), block=2).triangles == 10
    assert tcref.TCReference(3, *_coo([(0, 1)])).triangles == 0


@pytest.mark.parametrize("scale,seed", [(6, 1), (7, 2), (8, 1), (9, 3)])
def test_reference_equals_the_definition_on_the_generator_s_graphs(
        scale, seed):
    n, rows, cols, _ = graph.rmat_graph(scale, 16, seed)
    ref = tcref.TCReference(n, rows, cols, block=64)
    assert ref.triangles == tcref.brute_force(n, rows, cols) > 0
    assert ref.edges == len(rows) // 2
    # the order bounds the out-degree where the hubs' degrees are not
    assert ref.max_out_degree < graph.degrees(rows, n).max()
    with pytest.raises(ValueError, match="n <= 512"):
        tcref.brute_force(1024, rows, cols)


def test_check_refuses_what_no_tolerance_would_let_by():
    ref = tcref.TCReference(7, *_coo(EDGES))
    assert ref.check_count(2, 16, 7) is None
    assert ref.check_count(np.int64(2), np.int32(7), 7) is None
    bad = ref.check_count(3, 16, 7)
    assert bad == "3 triangles, the reference counts 2 (off by 1)"
    assert "6 edges of weight 1, the graph has 7" in ref.check_count(2, 16, 6)
    assert ref.check_count(2, 6, 7) == "6 pairs walked for 7 edges"
    both = ref.check_count(1, 16, 8)
    assert "off by -1" in both and "8 edges" in both
    # what is no triple of integers at all
    assert "not three integers" in ref.check_count(2.0, 16, 7)
    assert "not three integers" in ref.check_count(None, 16, 7)


# --- the driver ------------------------------------------------------------


def test_driver_holds_a_seeded_sample_to_the_reference_and_all_to_the_first():
    spec = _spec()
    assert spec.traffic(MIX)["driver"] == "library_count"
    drv = spec.load_module("drivers", "library_count")
    picker = spec.load_module("drivers", "library_job").checked_jobs
    ref = tcref.TCReference(7, *_coo(EDGES))
    good = [(2, 16, 7)] * 9
    picks = picker(7, 9, 4)
    assert picks[0] == 0 and picks[-1] == 8 and len(picks) == 6
    assert drv.check_jobs(ref, good, picks) == []
    # a job outside the sample that differs from the first is named
    quiet = next(k for k in range(9) if k not in picks)
    jobs = list(good)
    jobs[quiet] = (3, 16, 7)
    assert drv.check_jobs(ref, jobs, picks) == [
        f"job {quiet}: (triangles, pairs, edges) = (3, 16, 7), the first "
        "job's are (2, 16, 7)"]
    # every job wrong alike: the sample holds them to the reference
    found = drv.check_jobs(ref, [(3, 16, 7)] * 9, picks)
    assert len(found) == 6 and all("off by 1" in f for f in found)
    assert drv.LEAST_JOBS == 3


def test_driver_ends_the_run_at_once_on_a_program_without_the_entry():
    """The parent of the PR that added ``models/tc.py:tc_job``: the run
    ends before the graph is loaded, non-zero, with a sentence."""
    drv = _spec().load_module("drivers", "library_count")

    class Job:
        mix = {"entry": "combblas_tpu.models.tc:no_such_entry"}

        def deploy(self):
            raise AssertionError("the graph was loaded first")

    with pytest.raises(SystemExit) as e:
        drv.run(Job())
    assert "no 'combblas_tpu.models.tc:no_such_entry'" in str(e.value)
    assert e.value.code != 0


def test_the_control_refuses_its_three_altered_counts(tmp_path):
    """``python3 -m chipbench.tccontrol``: through the driver's own
    ``check_jobs``, a count off by one, the triple of the graph with one
    edge removed and a count without the ``hi`` half of its split come
    out NOT correct; untouched it comes out correct."""
    bench = small_benchmark(str(tmp_path), scale=10)
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.tccontrol", "--bench", bench,
         "--seed", "2300001111"],
        cwd=CHECKOUT, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = {o["alter"]: o for o in map(
        json.loads, r.stdout.strip().splitlines())}
    assert list(out) == ["none", "off_by_one", "edge_removed", "hi_dropped"]
    assert out["none"]["correct"] is True and out["none"]["problems"] == []
    t, pairs, edges = out["none"]["triple"]
    for how in list(out)[1:]:
        assert out[how]["correct"] is False and out[how]["checked"] == 6
    assert out["off_by_one"]["triple"] == [t + 1, pairs, edges]
    assert "off by 1" in out["off_by_one"]["problems"][0]
    less = out["edge_removed"]
    assert less["triple"][2] == edges - 1
    assert less["triple"][0] == t - less["closed"] and less["closed"] >= 0
    assert f"the graph has {edges} undirected edges" in less["problems"][0]
    assert out["hi_dropped"]["triple"][0] == (3 * t & 0x7FFF) // 3 < t
    # one alteration by name; the exit code says whether the check held
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.tccontrol", "--bench", bench,
         "--seed", "7", "--alter", "off_by_one"],
        cwd=CHECKOUT, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and len(r.stdout.strip().splitlines()) == 1


# --- the cost ---------------------------------------------------------------


def test_least_bytes_of_a_job_by_hand():
    n, nnz = 1 << 18, 7_611_536
    # the table once written, once read; two int32 a nonzero, twice
    assert tccost.tc_job_least_bytes(n, nnz) == (
        2 * 8_589_934_592 + 2 * 60_892_288) == 17_301_653_760
    assert tccost.tc_job_least_bytes(64, 10) == 2 * 512 + 160
    # what today's scan gathers: two 32 KB rows a pair slot
    assert tccost.gathered_bytes(7_618_560, n) == 7_618_560 * 65_536
    # a kernel that fetched every row once would still read under 100%
    assert tccost.tc_job_least_bytes(n, nnz) < tccost.gathered_bytes(
        7_618_560, n) / 25


# --- the readers -----------------------------------------------------------

MODULE = "jit_tc_edgeharvest_bits"
_PATH = "jit(tc_edgeharvest_bits)/"
_STEP = _PATH + "tc.harvest/while/body/closed_call/"
#: what ``combblas_tpu.obs.opnames`` would hold for the program
TABLE = {
    "fusion.1": _PATH + "tc.dedup/jit(argsort)/sort",
    "fusion.2": _PATH + "tc.dedup/gather",
    "fusion.3": _PATH + "tc.pack/broadcast_in_dim",
    "fusion.4": _PATH + "tc.pack/scatter-add",
    "fusion.5": _PATH + "tc.harvest/pad",
    "while.6": _PATH + "tc.harvest/while",
    "compare.7": _PATH + "tc.harvest/while/cond/lt",
    "fusion.8": _STEP + "gather/gather",
    "fusion.9": _STEP + "gather/gather",
    "fusion.10": _STEP + "popcount/population_count",
    "fusion.11": _STEP + "add",
    "fusion.12": _PATH + "reduce_sum",
}
_NAMES = list(TABLE) + ["copy.13", MODULE + "(5)"]
_ID = {name: i + 1 for i, name in enumerate(_NAMES)}
#: ns of the first row gather in each step of the two whole executions;
#: every other operation is fixed
STEPS = [[3000, 2000, 1000], [3000, 1000, 1000, 1000]]
SORT, TAKE, FILL, ADD, PAD, COND, ROWS, POP, ACC, SUM = (
    700, 100, 50, 900, 30, 10, 500, 400, 20, 40)
STEP = COND + ROWS + POP + ACC


def _ev(name: str, start: int, end: int) -> str:
    return (f"events {{ metadata_id: {_ID[name]} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} }}")


def _seq(t, steps):
    evs = []
    for name, ns in steps:
        evs.append(_ev(name, t, t + ns))
        t += ns
    return evs, t


def _execution(t0, gathers):
    evs, t = _seq(t0, [("fusion.1", SORT), ("fusion.2", TAKE),
                       ("fusion.3", FILL), ("fusion.4", ADD),
                       ("fusion.5", PAD)])
    loop0, body = t, []
    for g in gathers:
        part, t = _seq(t, [("compare.7", COND), ("fusion.8", g),
                           ("fusion.9", ROWS), ("fusion.10", POP),
                           ("fusion.11", ACC)])
        body += part
    part, t = _seq(t, [("compare.7", COND)])
    evs += [_ev("while.6", loop0, t)] + body + part
    part, t = _seq(t, [("fusion.12", SUM)])
    return evs + part, (t0, t)


def _trace() -> bytes:
    from jax.profiler import ProfileData

    ops, mods, t = [_ev("copy.13", 100, 200)], [], 1000
    for gathers in STEPS:
        evs, span = _execution(t, gathers)
        ops += evs
        mods.append(_ev(MODULE + "(5)", *span))
        t = span[1] + 1000
    # a third execution, cut by the trace's end
    ops.append(_ev("fusion.1", t, t + 1000))
    mods.append(_ev(MODULE + "(5)", t, t + 1000))
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: '
        f'"%{n} = u32[64]{{0}} fusion(%p)" }} }}'
        if not n.startswith(MODULE) else
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in _ID.items())
    lines = " ".join(
        f'lines {{ id: {k + 1} name: "{nm}" timestamp_ns: 0 '
        + " ".join(evs) + " }"
        for k, (nm, evs) in enumerate(
            (("XLA Modules", mods), ("XLA Ops", ops))))
    return ProfileData.text_proto_to_serialized_xspace(
        f'planes {{ id: 1 name: "/device:TPU:0" {lines} {meta} }}')


def test_scopes_of_this_kind_on_a_trace_with_the_harvest_s_scan():
    red = tcscopes.reduce_scopes(_trace(), {MODULE: TABLE})
    by = red["by_scope"]
    assert red["module"] == MODULE and red["executions"] == 2
    assert not any("bfs." in k or "bucket9" in k for k in by)
    assert set(by) == {"tc.dedup", "tc.pack", "tc.harvest",
                       "tc.harvest/gather", "tc.harvest/popcount"}
    steps = sum(map(len, STEPS))
    assert by["tc.dedup"] == pytest.approx((SORT + TAKE) * NS)
    assert by["tc.pack"] == pytest.approx((FILL + ADD) * NS)
    assert by["tc.harvest/gather"] == pytest.approx(
        (sum(map(sum, STEPS)) + steps * ROWS) / 2 * NS)
    assert by["tc.harvest/popcount"] == pytest.approx(steps / 2 * POP * NS)
    # the scan's own: the pad before it, its conditions, the accumulate
    assert by["tc.harvest"] == pytest.approx(
        (PAD + (steps + 2) / 2 * COND + steps / 2 * ACC) * NS)
    assert red["unscoped_s"] == pytest.approx(SUM * NS)
    assert sum(by.values()) + red["unscoped_s"] == pytest.approx(
        red["device_s"])
    # a step of each execution, the last one to the loop's end
    want = [[STEP + g for g in run[:-1]] + [STEP + run[-1] + COND]
            for run in STEPS]
    assert [[round(s / NS) for s in lv] for lv in red["levels"]] == want
    ctx = {"_scoped": red}
    assert tcscopes.scope_ms(ctx, ("tc.dedup", "tc.pack")) == pytest.approx(
        (SORT + TAKE + FILL + ADD) * 1e-6)
    assert tcscopes.scope_ms(ctx, ("tc.harvest",)) == pytest.approx(
        1e3 * (by["tc.harvest"] + by["tc.harvest/gather"]
               + by["tc.harvest/popcount"]))
    assert tcscopes.scope_ms(ctx, ("cc.iter",)) is None
    # the same trace under no table holds nothing of this kind
    bare = tcscopes.reduce_scopes(_trace(), {})
    assert bare["by_scope"] is None and bare["levels"] is None
    assert tcscopes.scope_ms({"_scoped": bare}, ("tc.harvest",)) is None
    # and scopes.py's own reading of a BFS trace is what it was
    import tiny_scoped_trace as T

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "tiny_scoped.xplane.pb")
    assert "bfs.level/ell.bucket0/gather" in scopes.reduce_scopes(
        path, {T.MODULE: T.TABLE})["by_scope"]


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_on_a_trace_and_none_without(name):
    from combblas_tpu import obs

    read = _spec().load_module("layers", name).read
    obs.reset()
    # nothing traced, no counter, no peak, a program without these
    # scopes: None, never 0 and never an exception
    assert read({"device": {"kind": "TPU v5 lite"}}) is None
    trace = _trace()
    least = tccost.tc_job_least_bytes(64, 10)
    empty = {"_scoped": tcscopes.reduce_scopes(trace, {}),
             "device": {"kind": "TPU v5 lite"}}
    assert read(empty) is None
    red = tcscopes.reduce_scopes(trace, {MODULE: TABLE})
    ctx = {"_scoped": red, "trace": devtrace.reduce_xplane(trace),
           "device": {"kind": "TPU v5 lite",
                      "memory_peak_bytes": 9_900_000_000},
           "least_bytes": least}
    obs.enable(install_hooks=False)
    try:
        for _ in range(3):  # the warm-up job and two more
            obs.count("models.tc.jobs")
            obs.count("models.tc.pairs", 16384)
            obs.count("models.tc.edges", 8000)
            obs.count("models.tc.triangles", 5)
        value = read(ctx)
    finally:
        obs.disable()
        obs.reset()
    device_s = red["device_s"]
    steps = sum(map(len, STEPS)) / 2
    harvest = (PAD + (steps + 1) * COND + sum(map(sum, STEPS)) / 2
               + steps * (ROWS + POP + ACC))
    want = {
        "tc_device_ms": 1e3 * device_s,
        "tc_pack_ms": (SORT + TAKE + FILL + ADD) * 1e-6,
        "tc_harvest_ms": harvest * 1e-6,
        "tc_pairs_per_edge": 2.048,
        "tc_hbm_share": 100 * (least / 819e9) / device_s,
        "tc_hbm_peak_gb": 9.9,
    }[name]
    assert value == pytest.approx(want)
    if name == "tc_hbm_share":
        assert 0 < value < 100


# --- what the cell added ----------------------------------------------------


def test_the_cell_its_configuration_and_its_six_readers_are_appended():
    """Order checks only: whatever a later PR appends, these hold."""
    spec = _spec()
    cells = [w["name"] for w in spec.doc["workloads"]]
    configs = [c["name"] for c in spec.doc["configs"]]
    assert cells.index(CC_CELL) < cells.index(CELL)
    assert configs.index(CC_CONFIG) < configs.index(CONFIG)
    assert sum(w["chips"] == 4 for w in spec.doc["workloads"]) == 1
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    reported = {m["name"] for m in spec.metrics_for(CELL, "end_to_end")}
    assert reported == {"mteps", "setup_s"}
    # it joined one list that was there, after the cells that were there
    joined = [m for sec in ("end_to_end", "per_layer")
              for m in spec.doc[sec] if CELL in m.get("workloads", ())]
    assert [m["name"] for m in joined] == ["mteps"] + READERS
    at = joined[0]["workloads"].index
    assert at("g500-s20.k2-batch") < at(CC_CELL) < at(CELL)
    # the six, in the issue's order, after the boot's six, listing this
    # cell alone
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert [n for n in names if n in READERS] == READERS
    assert names.index("boot_unspanned_s") < names.index(READERS[0])
    for m in joined[1:]:
        assert m["workloads"] == [CELL] and m["moves"] == "mteps"
        assert m["layer"] == "algorithms + local kernels"
    by = {m["name"]: m for m in joined}
    assert [(by[n]["unit"], by[n]["better"], by[n]["source"])
            for n in READERS] == [
        ("ms", "lower", "device_trace"), ("ms", "lower", "device_trace"),
        ("ms", "lower", "device_trace"),
        ("pairs", "lower", "program_counter"),
        ("%", "higher", "device_trace"), ("GB", "lower", "program_counter")]
    mine = {m["name"] for m in spec.metrics_for(CELL, "per_layer")}
    assert set(READERS) | {"compiles_in_window", "load_s",
                           "warmup_s"} <= mine
    assert not any(m.startswith(("bfs_", "k2_", "cc_")) for m in mine)
    # and no other cell reports them
    for other in cells:
        if other != CELL:
            assert not set(READERS) & {m["name"] for m in spec.metrics_for(
                other, "per_layer")}


def test_the_configuration_states_its_cut_and_its_guarantees():
    spec = _spec()
    cfg = spec.config(CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["scale"] == list(cfg["reduced"])
    assert "HBM" in cfg["reduced"]["scale"] or "memory" in cfg["reduced"][
        "scale"]
    assert (cfg["grid"], cfg["scale"], cfg["edgefactor"],
            cfg["graph_seed"]) == ([1, 1], 18, 16, 1)
    assert cfg["kinds"] == [] and cfg["keep_coo"] is False
    # the generator's shape is every one-chip configuration's
    base = spec.config("g500-s20-1x1")
    assert all(cfg[k] == base[k] for k in (
        "grid", "edgefactor", "graph_seed", "rmat"))
    assert {"count", "simple_graph", "job", "graph_seed",
            "upload"} <= set(cfg["assumed"])
    assert "draws NOTHING a job reads" in cfg["assumed"]["graph_seed"]
    assert {"count", "jobs", "window"} == set(cfg["guarantees"])
    assert "equality" in cfg["guarantees"]["count"]
    mix = spec.traffic(MIX)
    assert mix["entry"] == "combblas_tpu.models.tc:tc_job"
    assert mix["check"] == {"sampled": 4}
    # the slice holds two whole jobs
    assert mix["trace"]["start_s"] + mix["trace"]["seconds"] <= 45


# --- the cell, rehearsed ----------------------------------------------------


def test_the_cell_through_the_real_command(tmp_path):
    bench = small_benchmark(str(tmp_path), scale=8)
    # (a seed beyond 32 signed bits, as the driver's are)
    r, line = run_cell(bench, CELL, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"mteps", "setup_s"} and m["mteps"] > 0
    n, rows, cols, _ = graph.rmat_graph(8, 16, 1)
    count = tcref.brute_force(n, rows, cols)
    assert (f"tc: the reference counts {count} triangles over "
            f"{len(rows) // 2} undirected edges") in r.stderr
    assert (f"tc: the first job {count} triangles, 8192 pairs walked for "
            f"{len(rows) // 2} edges") in r.stderr
    assert "(limit: equality)" in r.stderr
    r, line = run_cell(bench, CELL, trace=1, seed=4, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"deployment {CONFIG}: snapshot" in r.stderr
    m = check_line(line)
    assert {"load_s", "warmup_s", "compiles_in_window",
            "tc_pairs_per_edge"} <= set(m)
    assert m["compiles_in_window"] == 0
    assert m["tc_pairs_per_edge"] == pytest.approx(
        8192 / (len(rows) // 2))
    # the device trace's readers find no device plane on a CPU: left
    # out of the line, never 0
    assert not set(READERS) - {"tc_pairs_per_edge"} & set(m)
