"""GAP's CC kernel's part of the benchmark without the chip: the plain
reference against hand-made labels (a merged pair, a split component, a
label that is not the smallest id), the driver's checks and its fast
exit on a program without the entry, the cost function by hand, the six
readers on a small trace of a program with the FastSV scopes and without
them, what the cell added to ``BENCHMARK.json``, the control, and one
rehearsal of ``g500-s20cc.cc-batch`` through the real command at scale
9."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import cccost, ccref, ccscopes, devtrace, scopes
from chipbench.spec import CHECKOUT, Spec

from rehearse import check_line, run_cell, small_benchmark

NS = 1e-9
CELL, CONFIG, MIX = "g500-s20cc.cc-batch", "g500-s20-cc-1x1", "cc-batch"
BC_CELL, BC_CONFIG = "g500-s20bc.bc-sat", "g500-s20-bc-1x1"
READERS = ["cc_device_ms", "cc_round_ms", "cc_rounds", "cc_spmv_share",
           "cc_hook_share", "cc_hbm_share"]


def _spec():
    return Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))


# --- the reference against hand-made labels ---------------------------------

#   1 -- 4 -- 6        2 -- 3        0    5    7
EDGES = [(1, 4), (4, 6), (2, 3)]
LABELS = np.array([0, 1, 2, 2, 1, 5, 1, 7], np.int32)


@pytest.fixture(scope="module")
def ref():
    r = np.array([e[0] for e in EDGES] + [e[1] for e in EDGES])
    c = np.array([e[1] for e in EDGES] + [e[0] for e in EDGES])
    return ccref.CCReference(8, r, c)


def test_reference_labels_a_component_with_its_smallest_vertex(ref):
    assert np.array_equal(ref.labels, LABELS)
    assert ref.labels.dtype == np.int32
    assert (ref.components, ref.largest) == (5, 3)
    assert ref.check_labels(LABELS) is None
    assert ref.check_labels(LABELS.astype(np.int64)) is None


def test_check_refuses_what_no_tolerance_would_let_by(ref):
    merged = LABELS.copy()
    merged[[2, 3]] = 1  # 2-3 joined to 1-4-6
    bad = ref.check_labels(merged)
    assert bad.startswith("2 of 8 labels differ") and "vertex 2" in bad
    assert "4 components where the reference has 5" in bad
    assert "the largest 5 where it has 3" in bad
    split = LABELS.copy()
    split[6] = 6  # one end of the edge 4-6 on its own
    bad = ref.check_labels(split)
    assert "1 of 8 labels differ" in bad
    assert "vertex 6 is labelled 6, the reference says 1" in bad
    assert "6 components where the reference has 5" in bad
    other = LABELS.copy()
    other[[1, 4, 6]] = 6  # the right partition under another name
    bad = ref.check_labels(other)
    assert "3 of 8 labels differ" in bad
    assert "5 components where the reference has 5" in bad
    assert "not each component's smallest vertex id" in bad
    assert "smallest vertex id" not in ref.check_labels(split)
    # what is no label array at all
    assert "not one integer a vertex" in ref.check_labels(LABELS[:7])
    assert "not one integer a vertex" in ref.check_labels(
        LABELS.astype(np.float32))


# --- the driver ------------------------------------------------------------


def test_driver_checks_the_first_the_last_and_a_seeded_sample(ref):
    spec = _spec()
    assert spec.traffic(MIX)["driver"] == "library_job"
    drv = spec.load_module("drivers", "library_job")
    picks = drv.checked_jobs(2300001111, 20, 4)
    assert picks[0] == 0 and picks[-1] == 19 and len(set(picks)) == 6
    assert picks == drv.checked_jobs(2300001111, 20, 4)
    assert picks != drv.checked_jobs(7, 20, 4)
    assert drv.checked_jobs(7, 1, 4) == [0]
    assert drv.checked_jobs(7, 3, 4) == [0, 1, 2]
    good = [(LABELS.copy(), 4, 2) for _ in range(20)]
    picks = drv.checked_jobs(7, 20, 4)
    assert drv.check_jobs(ref, good, picks) == []
    # a job outside the sample that differs from the first is named
    quiet = next(k for k in range(20) if k not in picks)
    wrong = LABELS.copy()
    wrong[6] = 6
    jobs = list(good)
    jobs[quiet] = (wrong, 4, 3)
    found = drv.check_jobs(ref, jobs, picks)
    assert found == [
        f"job {quiet}: 1 labels are not the first job's",
        f"job {quiet}: 4 rounds and 3 jumps, the first job ran 4 and 2"]
    # every job wrong alike: the sample holds them to the reference
    found = drv.check_jobs(ref, [(wrong, 4, 2)] * 20, picks)
    assert len(found) == 6 and all("the reference says 1" in f for f in found)


def test_driver_ends_the_run_at_once_on_a_program_without_the_entry():
    """The parent of the PR that added ``models/cc.py:fastsv``: the run
    ends before the graph is loaded, non-zero, with a message."""
    drv = _spec().load_module("drivers", "library_job")

    class Job:
        mix = {"entry": "combblas_tpu.models.cc:no_such_entry"}

        def deploy(self):
            raise AssertionError("the graph was loaded first")

    with pytest.raises(SystemExit) as e:
        drv.run(Job())
    assert "no 'combblas_tpu.models.cc:no_such_entry'" in str(e.value)
    assert e.value.code != 0


def test_the_control_through_the_cell_s_own_checks(tmp_path):
    """``python3 -m chipbench.cccontrol``: the reference's labels with
    ONE edge's two ends relabelled apart come out NOT correct through the
    driver's ``check_jobs``; untouched they come out correct."""
    bench = small_benchmark(str(tmp_path), scale=10)

    def control(edges):
        r = subprocess.run(
            [sys.executable, "-m", "chipbench.cccontrol", "--bench", bench,
             "--seed", "2300001111", "--edges", str(edges)],
            cwd=CHECKOUT, capture_output=True, text=True, timeout=300)
        return r, json.loads(r.stdout.strip().splitlines()[-1])

    r, out = control(1)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is False and out["checked"] == 6
    (a, b), = out["apart"]
    assert a != b and "1 of 1024 labels differ" in out["problems"][0]
    r, out = control(0)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is True and out["problems"] == []


# --- the cost ---------------------------------------------------------------


def test_least_bytes_of_a_job_by_hand():
    n, slots = 1 << 20, 36_953_104
    # an index a slot; the n + 1 table; u, f and the new f
    assert cccost.cc_round_least_bytes(slots, n) == (
        147_812_416 + 4_194_308 + 12_582_912) == 164_589_636
    assert cccost.cc_job_least_bytes(slots, n, 5, 2) == (
        5 * 164_589_636 + 2 * 8_388_608)
    assert cccost.cc_job_least_bytes(96, 64, 3, 1) == (
        3 * (4 * 96 + 4 * 65 + 12 * 64) + 8 * 64)


# --- the readers -----------------------------------------------------------

MODULE = "jit_cc_fastsv_ell"
_PATH = "jit(cc_fastsv_ell)/"
_ITER = _PATH + "cc.iter/while/body/"
_SWEEP = _ITER + "cc.spmv/jit(dist_spmv_ell)/"
#: what ``combblas_tpu.obs.opnames`` would hold for the program
TABLE = {
    "fusion.9": _PATH + "cc.init/iota",
    "while.5": _PATH + "cc.iter/while",
    "compare.7": _PATH + "cc.iter/while/cond/and",
    "fusion.1": _ITER + "cc.gather/gather",
    "fusion.2": _SWEEP + "ell.bucket0/gather/gather",
    "fusion.3": _SWEEP + "ell.bucket0/fold/reduce_min",
    "fusion.4": _SWEEP + "ell.bucket0/scatter_rows/scatter-min",
    "fusion.12": _SWEEP + "concatenate",
    "fusion.6": _ITER + "cc.hook/scatter-min",
    "fusion.8": _ITER + "cc.min/min",
    "while.10": _PATH + "cc.jump/while",
    "fusion.11": _PATH + "cc.jump/while/body/gather",
}
_NAMES = list(TABLE) + ["copy.13", MODULE + "(5)"]
_ID = {name: i + 1 for i, name in enumerate(_NAMES)}
#: ns of the sweep's gather in each round of the two whole executions;
#: every other operation of a round is fixed
ROUNDS = [[3000, 2000, 1000], [3000, 1000, 1000, 1000]]
JUMPS = [1, 2]
COND, SUB, TAB, FOLD, ROWS, HOOK, MIN, JUMP, EDGE = (
    10, 100, 20, 200, 150, 400, 120, 100, 500)
ROUND = COND + SUB + TAB + FOLD + ROWS + HOOK + MIN


def _ev(name: str, start: int, end: int) -> str:
    return (f"events {{ metadata_id: {_ID[name]} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} }}")


def _seq(t, steps):
    evs = []
    for name, ns in steps:
        evs.append(_ev(name, t, t + ns))
        t += ns
    return evs, t


def _execution(t0, gathers, jumps):
    evs, t = [_ev("fusion.9", t0, t0 + EDGE)], t0 + EDGE
    loop0 = t
    body = []
    for g in gathers:
        part, t = _seq(t, [
            ("compare.7", COND), ("fusion.1", SUB), ("fusion.12", TAB),
            ("fusion.2", g), ("fusion.3", FOLD), ("fusion.4", ROWS),
            ("fusion.6", HOOK), ("fusion.8", MIN)])
        body += part
    part, t = _seq(t, [("compare.7", COND)])
    evs += [_ev("while.5", loop0, t)] + body + part
    jump0 = t
    part, t = _seq(t, [("fusion.11", JUMP)] * jumps)
    evs += [_ev("while.10", jump0, t)] + part
    return evs, (t0, t)


def _trace() -> bytes:
    from jax.profiler import ProfileData

    ops, mods, t = [_ev("copy.13", 100, 200)], [], 1000
    for gathers, jumps in zip(ROUNDS, JUMPS):
        evs, span = _execution(t, gathers, jumps)
        ops += evs
        mods.append(_ev(MODULE + "(5)", *span))
        t = span[1] + 1000
    # a third execution, cut by the trace's end
    ops.append(_ev("fusion.9", t, t + 1000))
    mods.append(_ev(MODULE + "(5)", t, t + 1000))
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: '
        f'"%{n} = s32[64]{{0}} fusion(%p)" }} }}'
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


def test_scopes_of_this_kind_on_a_trace_with_the_fastsv_loop():
    red = ccscopes.reduce_scopes(_trace(), {MODULE: TABLE})
    by = red["by_scope"]
    assert red["module"] == MODULE and red["executions"] == 2
    assert not any("bfs." in k or "bucket9" in k for k in by)
    assert set(by) == {
        "cc.init", "cc.iter", "cc.iter/cc.gather", "cc.iter/cc.spmv",
        "cc.iter/cc.spmv/ell.bucket0/gather",
        "cc.iter/cc.spmv/ell.bucket0/fold",
        "cc.iter/cc.spmv/ell.bucket0/scatter_rows",
        "cc.iter/cc.hook", "cc.iter/cc.min", "cc.jump"}
    rounds = sum(map(len, ROUNDS))
    assert by["cc.init"] == pytest.approx(EDGE * NS)
    assert by["cc.iter/cc.hook"] == pytest.approx(rounds / 2 * HOOK * NS)
    assert by["cc.iter/cc.spmv"] == pytest.approx(rounds / 2 * TAB * NS)
    assert by["cc.iter/cc.spmv/ell.bucket0/gather"] == pytest.approx(
        sum(map(sum, ROUNDS)) / 2 * NS)
    # the loop's own: its conditions
    assert by["cc.iter"] == pytest.approx((rounds + 2) / 2 * COND * NS)
    assert by["cc.jump"] == pytest.approx(sum(JUMPS) / 2 * JUMP * NS)
    assert red["unscoped_s"] == 0
    assert sum(by.values()) == pytest.approx(red["device_s"])
    # the last round runs to the loop's end: the condition's last reading
    want = [[ROUND + g for g in run[:-1]] + [ROUND + run[-1] + COND]
            for run in ROUNDS]
    assert [[round(s / NS) for s in lv] for lv in red["levels"]] == want
    ctx = {"_scoped": red}
    # the median of 3000 2000 1010, 3000 1000 1000 1010 (+ ROUND each)
    assert ccscopes.round_ms(ctx) == pytest.approx((ROUND + 1010) * 1e-6)
    # the same trace under no table, or BFS's, holds nothing of this kind
    bare = ccscopes.reduce_scopes(_trace(), {})
    assert bare["by_scope"] is None and bare["levels"] is None
    assert ccscopes.round_ms({"_scoped": bare}) is None
    assert ccscopes.share({"_scoped": bare}, ("cc.spmv",)) is None
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
    # nothing traced, no counter, a program without these scopes: None
    assert read({"device": {"kind": "TPU v5 lite"}}) is None
    trace = _trace()
    least = cccost.cc_job_least_bytes(96, 64, 3.5, 1.5)
    empty = {"_scoped": ccscopes.reduce_scopes(trace, {}),
             "device": {"kind": "TPU v5 lite"}}
    assert read(empty) is None
    red = ccscopes.reduce_scopes(trace, {MODULE: TABLE})
    ctx = {"_scoped": red, "trace": devtrace.reduce_xplane(trace),
           "device": {"kind": "TPU v5 lite"}, "least_bytes": least}
    obs.enable(install_hooks=False)
    try:
        for _ in range(3):  # the warm-up job and two more
            obs.count("models.cc.jobs")
            obs.count("models.cc.rounds", 5)
            obs.count("models.cc.jumps", 2)
        value = read(ctx)
    finally:
        obs.disable()
        obs.reset()
    device_s = red["device_s"]
    rounds = sum(map(len, ROUNDS)) / 2
    sweep = (sum(map(sum, ROUNDS)) / 2 + rounds * (TAB + FOLD + ROWS)) * NS
    want = {
        "cc_device_ms": 1e3 * device_s,
        "cc_round_ms": (ROUND + 1010) * 1e-6,
        "cc_rounds": 5.0,
        "cc_spmv_share": 100 * sweep / device_s,
        "cc_hook_share": 100 * rounds * (HOOK + SUB) * NS / device_s,
        "cc_hbm_share": 100 * (least / 819e9) / device_s,
    }[name]
    assert value == pytest.approx(want)
    if name.endswith("_share"):
        assert 0 < value < 100


# --- what the cell added ----------------------------------------------------


def test_the_cell_is_appended_and_its_readers_wait_for_a_benchmark_pr():
    spec = _spec()
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert not set(READERS) & set(names)
    drv = spec.load_module("drivers", "library_job")
    assert list(drv.LAYERS) == READERS
    # after the BC cell, wherever later cells go: no place is pinned
    cells = [w["name"] for w in spec.doc["workloads"]]
    configs = [c["name"] for c in spec.doc["configs"]]
    assert cells.index(BC_CELL) < cells.index(CELL)
    assert configs.index(BC_CONFIG) < configs.index(CONFIG)
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    reported = {m["name"] for m in spec.metrics_for(CELL, "end_to_end")}
    assert reported == {"mteps", "setup_s"}
    # it joined one list, after the cell that was there
    joined = [m for sec in ("end_to_end", "per_layer")
              for m in spec.doc[sec] if CELL in m.get("workloads", ())]
    assert [m["name"] for m in joined] == ["mteps"]
    at = joined[0]["workloads"].index
    assert at("g500-s20.k2-batch") < at(CELL)
    # and reports the three per-layer metrics every cell reports
    mine = {m["name"] for m in spec.metrics_for(CELL, "per_layer")}
    assert mine == {"compiles_in_window", "load_s", "warmup_s"}
    cfg = spec.config(CONFIG)
    assert list(cfg["reduced"]) == ["scale"] and cfg["kinds"] == []
    assert (cfg["scale"], cfg["edgefactor"], cfg["graph_seed"]) == (20, 16, 1)
    assert cfg["keep_coo"] is False and "lane_widths" not in cfg
    entry = spec.doc["configs"][configs.index(CONFIG)]
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["scale"]
    assert {"labels", "job", "graph_seed"} <= set(cfg["assumed"])
    assert "draws NOTHING a job reads" in cfg["assumed"]["graph_seed"]
    assert {"labels", "jobs", "window"} == set(cfg["guarantees"])
    # the graph is g500-s20-1x1's: same generator, same seed
    base = spec.config("g500-s20-1x1")
    assert all(cfg[k] == base[k] for k in (
        "grid", "scale", "edgefactor", "graph_seed", "rmat"))
    mix = spec.traffic(MIX)
    assert mix["entry"] == "combblas_tpu.models.cc:fastsv"
    assert mix["check"] == {"sampled": 4}


# --- the cell, rehearsed ----------------------------------------------------


def test_the_cell_through_the_real_command(tmp_path):
    bench = small_benchmark(str(tmp_path))
    # (a seed beyond 32 signed bits, as the driver's are)
    r, line = run_cell(bench, CELL, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"mteps", "setup_s"} and m["mteps"] > 0
    assert "against the reference on all entries" in r.stderr
    assert "cc: every job 4 rounds and 1 jumps" in r.stderr
    r, line = run_cell(bench, CELL, trace=1, seed=4, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"deployment {CONFIG}: snapshot" in r.stderr
    m = check_line(line)
    assert set(m) == {"load_s", "warmup_s", "compiles_in_window"}
    assert m["compiles_in_window"] == 0
    # the kind's own readings are logged, not in the line: the counter's
    # on any platform, the device trace's only where there is a device plane
    logged = dict(ln.split("layer ", 1)[1].split(": ", 1)
                  for ln in r.stderr.splitlines() if "layer cc_" in ln)
    assert list(logged) == READERS
    assert float(logged.pop("cc_rounds")) == 4.0
    assert set(logged.values()) == {"nothing to read"}
