"""The matching cell's part of the benchmark without the chip: the
bipartite generator a function of its seed, the plain reference against
hand-made mates and the control's two faults, the driver's checks and
its fast exit on a program without the entry, the cost function by hand,
the eight readers on a small trace of a program with the job's scopes
and without them, what the cell added to ``BENCHMARK.json``, and one
rehearsal of ``g500-mcm.mcm-batch`` through the real command at scale
9."""

import json
import os

import numpy as np
import pytest

from chipbench import (
    devtrace, mcmcontrol, mcmcost, mcmgraph, mcmref, mcmscopes, mcmwork)
from chipbench.spec import CHECKOUT, Spec

from rehearse import check_line, run_cell, small_benchmark

NS = 1e-9
CELL, CONFIG, MIX = "g500-mcm.mcm-batch", "g500-mcm-1x1", "mcm-batch"
READERS = ["mcm_device_ms", "mcm_init_ms", "mcm_phase_ms", "mcm_phases",
           "mcm_push_share", "mcm_host_gap_ms", "mcm_hbm_share",
           "mcm_hbm_peak_gb"]


def _spec():
    return Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))


# --- the generator ----------------------------------------------------------


def test_the_pattern_is_a_function_of_its_seed_and_not_symmetrised():
    nr, nc, rows, cols = mcmgraph.bipartite_rmat(9, 16, 1)
    again = mcmgraph.bipartite_rmat(9, 16, 1)
    assert (nr, nc) == (512, 512) == again[:2]
    assert (rows == again[2]).all() and (cols == again[3]).all()
    assert rows.dtype == cols.dtype == np.int32
    keys = rows.astype(np.int64) * nc + cols
    assert (np.diff(keys) > 0).all()  # sorted, no nonzero twice
    other = mcmgraph.bipartite_rmat(9, 16, 2)
    assert len(other[2]) != len(rows) or (other[2] != rows).any()
    # one-directional: most nonzeros have no mirror image
    mirrored = np.isin(cols.astype(np.int64) * nc + rows, keys)
    assert mirrored.mean() < 0.2
    # R-MAT's skew on both sides, rows and columns permuted apart
    assert np.bincount(rows, minlength=nr).max() > 8 * 16
    assert np.bincount(cols, minlength=nc).max() > 8 * 16
    assert (np.bincount(rows, minlength=nr) == 0).sum() > nr // 8
    assert 0.5 * 16 * nr < len(rows) < 16 * nr


# --- the reference against hand-made mates ----------------------------------

#   rows 0..3, columns 0..3; the one maximum matching has 3 pairs
#   r0: c0 c1   r1: c0   r2: c0   r3: c3
ROWS = np.array([0, 0, 1, 2, 3])
COLS = np.array([0, 1, 0, 0, 3])


@pytest.fixture(scope="module")
def ref():
    return mcmref.McmReference(4, 4, ROWS, COLS)


def _mates(pairs, nr=4, nc=4):
    mr, mc = np.full(nr, -1, np.int32), np.full(nc, -1, np.int32)
    for r, c in pairs:
        mr[r], mc[c] = c, r
    return mr, mc


def test_reference_takes_any_maximum_matching_and_no_other(ref):
    assert ref.cardinality == 3 and ref.nnz == 5
    assert ref.check(*_mates([(0, 1), (1, 0), (3, 3)])) is None
    assert ref.check(*_mates([(0, 1), (2, 0), (3, 3)])) is None
    # one pair short: a matching, not the maximum
    assert "2 pairs are matched, the maximum is 3" in ref.check(
        *_mates([(0, 0), (3, 3)]))
    # a pair that is no stored nonzero
    assert "no stored nonzero: row 1 with column 1" in ref.check(
        *_mates([(0, 0), (1, 1), (3, 3)]))
    # a column given to two rows: the mates are not each other's inverse
    mr, mc = _mates([(0, 1), (1, 0), (3, 3)])
    mr[2] = 0
    assert "not their column's mate: row 2" in ref.check(mr, mc)
    mr, mc = _mates([(0, 1), (1, 0), (3, 3)])
    mc[2] = 0
    assert "not their row's mate: column 2" in ref.check(mr, mc)
    # the wrong shape, type or range
    assert "not one integer a vertex" in ref.check(mr[:3], mc)
    assert "not one integer a vertex" in ref.check(mr.astype(float), mc)
    mr, mc = _mates([(0, 1)])
    mr[1] = 9
    assert "outside -1 .. 3" in ref.check(mr, mc)


def test_the_control_s_two_faults_fail_one_limit_each():
    nr, nc, rows, cols = mcmgraph.bipartite_rmat(8, 16, 1)
    ref = mcmref.McmReference(nr, nc, rows, cols)
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import maximum_bipartite_matching

    mc = maximum_bipartite_matching(csr_matrix(
        (np.ones(len(rows), np.int8), (rows, cols)), shape=(nr, nc)),
        perm_type="row").astype(np.int32)
    mr = np.full(nr, -1, np.int32)
    mr[mc[mc >= 0]] = np.flatnonzero(mc >= 0)
    assert ref.check(mr, mc) is None
    rng = np.random.default_rng(5)
    a, b, (r, c) = mcmcontrol.undo_one(mr, mc, rng)
    assert a[r] == -1 == b[c] and mr[r] == c
    assert f"{ref.cardinality - 1} pairs are matched" in ref.check(a, b)
    a, b, (r, c) = mcmcontrol.stray_pair(ref, mr, mc, rng)
    assert a[r] == c and b[c] == r and not ref.is_edge([r], [c])[0]
    assert (a >= 0).sum() == ref.cardinality
    bad = ref.check(a, b)
    assert "no stored nonzero" in bad and "the maximum" not in bad


def test_driver_checks_the_first_the_last_and_a_seeded_sample(ref):
    drv = _spec().load_module("drivers", "library_match")
    assert drv.checked_jobs(7, 2, 4) == [0, 1]
    picks = drv.checked_jobs(7, 20, 4)
    assert picks[0] == 0 and picks[-1] == 19 and len(picks) == 6
    assert picks == drv.checked_jobs(7, 20, 4) != drv.checked_jobs(8, 20, 4)
    good = (*_mates([(0, 1), (1, 0), (3, 3)]), 3, 2)
    assert drv.check_jobs(ref, [good] * 20, picks) == []
    # a job that is not checked against the reference still has to be
    # the first job over again
    other = (*_mates([(0, 1), (2, 0), (3, 3)]), 3, 2)
    k = next(j for j in range(20) if j not in picks)
    bad = drv.check_jobs(ref, [good] * k + [other] + [good] * (19 - k), picks)
    assert len(bad) == 1 and f"job {k}: 2 rows' mates" in bad[0]
    slow = good[:3] + (3,)
    bad = drv.check_jobs(ref, [good] * 19 + [slow], picks)
    assert bad == ["job 19: cardinality 3 in 3 phases, the first job's "
                   "was 3 in 2"]
    short = (*_mates([(0, 0), (3, 3)]), 2, 2)
    assert "job 0: 2 pairs are matched" in drv.check_jobs(
        ref, [short] * 20, picks)[0]
    lying = good[:2] + (4, 2)
    assert "says its cardinality is 4" in drv.check_jobs(
        ref, [lying] * 20, picks)[0]


def test_driver_ends_the_run_at_once_on_a_program_without_the_entry():
    drv = _spec().load_module("drivers", "library_match")

    class Job:
        mix = {"entry": "combblas_tpu.models.matching:no_such_job"}

        def deploy(self):
            raise AssertionError("the pattern was loaded first")

    with pytest.raises(SystemExit) as e:
        drv.run(Job())
    assert "has no 'combblas_tpu.models.matching:no_such_job'" in str(
        e.value)


def test_least_bytes_of_a_job_by_hand():
    # every nonzero's index once, both mate vectors once
    assert mcmcost.mcm_job_least_bytes(16_086_381, 1 << 20, 1 << 20) == (
        4 * 16_086_381 + 2 * 4_194_304) == 72_734_132
    assert mcmcost.mcm_job_least_bytes(5, 4, 3) == 48


# --- the readers -----------------------------------------------------------

MODULE = "jit__mcm_job_ell"
_PATH = "jit(_mcm_job_ell)/"
_INIT = _PATH + "mcm.init/while/body/"
_PHASE = _PATH + "mcm.phase/while/body/"
_LAYER = _PHASE + "mcm.bfs/while/body/"
#: what ``combblas_tpu.obs.opnames`` would hold for the program
TABLE = {
    "fusion.1": _PATH + "mcm.init/reduce_sum",
    "while.2": _PATH + "mcm.init/while",
    "fusion.3": _INIT + "cond/branch_0_fun/mcm.init.push/push.walk/gather",
    "fusion.4": _INIT + "cond/branch_1_fun/mcm.init.sweep/"
                        "ell.bucket0/gather/gather",
    "fusion.5": _INIT + "scatter-max",
    "while.6": _PATH + "mcm.phase/while",
    "compare.7": _PATH + "mcm.phase/while/cond/ne",
    "while.8": _PHASE + "mcm.bfs/while",
    "fusion.9": _LAYER + "cond/branch_0_fun/mcm.bfs.push/push.scatter/"
                         "scatter-max",
    "fusion.10": _LAYER + "cond/branch_1_fun/mcm.bfs.sweep/"
                          "ell.bucket0/fold/reduce_max",
    "fusion.11": _LAYER + "gather",
    "fusion.12": _PHASE + "mcm.chase/while/body/gather",
    "fusion.13": _PHASE + "mcm.augment/while/body/scatter-min",
}
_NAMES = list(TABLE) + ["copy.14", MODULE + "(5)"]
_ID = {name: i + 1 for i, name in enumerate(_NAMES)}
#: ns of the layers of each phase of the two whole executions (a phase
#: with no layer walked sweeps one); every other operation is fixed
PHASES = [[[300, 200], [100]], [[300, 200, 100], [100], [50]]]
EDGE, WALK, SWEEP, GRANT, COND, NEXT, CHASE, FLIP = (
    500, 40, 900, 60, 10, 30, 70, 90)
ROUNDS = 3  # Karp-Sipser rounds an execution: two walked, one swept


def _ev(name: str, start: int, end: int) -> str:
    return (f"events {{ metadata_id: {_ID[name]} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} }}")


def _seq(t, steps):
    evs = []
    for name, ns in steps:
        evs.append(_ev(name, t, t + ns))
        t += ns
    return evs, t


def _execution(t0, phases):
    evs, t = [_ev("fusion.1", t0, t0 + EDGE)], t0 + EDGE
    loop0 = t
    body, t = _seq(t, [("fusion.3", WALK), ("fusion.5", GRANT),
                       ("fusion.4", SWEEP), ("fusion.5", GRANT),
                       ("fusion.3", WALK), ("fusion.5", GRANT)])
    evs += [_ev("while.2", loop0, t)] + body
    loop0, body = t, []
    for layers in phases:
        part, t = _seq(t, [("compare.7", COND)])
        body += part
        bfs0, inner = t, []
        for k, ns in enumerate(layers):
            part, t = _seq(t, [
                ("fusion.9" if k else "fusion.10", ns), ("fusion.11", NEXT)])
            inner += part
        body += [_ev("while.8", bfs0, t)] + inner
        part, t = _seq(t, [("fusion.12", CHASE), ("fusion.13", FLIP)])
        body += part
    part, t = _seq(t, [("compare.7", COND)])
    evs += [_ev("while.6", loop0, t)] + body + part
    return evs, (t0, t)


def _trace() -> bytes:
    from jax.profiler import ProfileData

    ops, mods, t = [_ev("copy.14", 100, 200)], [], 1000
    for phases in PHASES:
        evs, span = _execution(t, phases)
        ops += evs
        mods.append(_ev(MODULE + "(5)", *span))
        t = span[1] + 1000
    # a third execution, cut by the trace's end
    ops.append(_ev("fusion.1", t, t + 1000))
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


def _phase_ns(layers):
    return COND + sum(layers) + NEXT * len(layers) + CHASE + FLIP


def test_scopes_of_this_kind_on_a_trace_with_the_job_s_loops():
    red = mcmscopes.reduce_scopes(_trace(), {MODULE: TABLE})
    by = red["by_scope"]
    assert red["module"] == MODULE and red["executions"] == 2
    assert not any("bfs.level" in k or "bucket9" in k for k in by)
    assert set(by) == {
        "mcm.init", "mcm.init/mcm.init.push",
        "mcm.init/mcm.init.sweep/ell.bucket0/gather",
        "mcm.phase", "mcm.phase/mcm.bfs", "mcm.phase/mcm.bfs/mcm.bfs.push",
        "mcm.phase/mcm.bfs/mcm.bfs.sweep/ell.bucket0/fold",
        "mcm.phase/mcm.chase", "mcm.phase/mcm.augment"}
    phases = sum(map(len, PHASES)) / 2
    layers = sum(len(p) for run in PHASES for p in run) / 2
    assert by["mcm.init"] == pytest.approx((EDGE + ROUNDS * GRANT) * NS)
    assert by["mcm.init/mcm.init.push"] == pytest.approx(2 * WALK * NS)
    assert by["mcm.phase/mcm.chase"] == pytest.approx(phases * CHASE * NS)
    assert by["mcm.phase/mcm.augment"] == pytest.approx(phases * FLIP * NS)
    assert by["mcm.phase/mcm.bfs"] == pytest.approx(layers * NEXT * NS)
    # the loop's own: its conditions
    assert by["mcm.phase"] == pytest.approx((phases + 1) * COND * NS)
    assert red["unscoped_s"] == 0
    assert sum(by.values()) == pytest.approx(red["device_s"])
    # the last phase runs to the loop's end: the condition's last reading
    want = [[_phase_ns(p) for p in run[:-1]] + [_phase_ns(run[-1]) + COND]
            for run in PHASES]
    assert [[round(s / NS) for s in lv] for lv in red["levels"]] == want
    ctx = {"_scoped": red}
    flat = sorted(s for lv in want for s in lv)
    assert mcmscopes.phase_ms(ctx) == pytest.approx(flat[2] * 1e-6)
    assert mcmscopes.under_ms(ctx, "mcm.init") == pytest.approx(
        (EDGE + ROUNDS * GRANT + 2 * WALK + SWEEP) * 1e-6)
    # the same trace under no table holds nothing of this kind
    bare = mcmscopes.reduce_scopes(_trace(), {})
    assert bare["by_scope"] is None and bare["levels"] is None
    assert mcmscopes.phase_ms({"_scoped": bare}) is None
    assert mcmscopes.under_ms({"_scoped": bare}, "mcm.init") is None


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_on_a_trace_and_none_without(name):
    from combblas_tpu import obs

    read = _spec().load_module("layers", name).read
    obs.reset()
    # nothing traced, no counter, a program without these scopes: None
    assert read({"device": {"kind": "TPU v5 lite"}}) is None
    trace = _trace()
    least = mcmcost.mcm_job_least_bytes(5000, 512, 512)
    empty = {"_scoped": mcmscopes.reduce_scopes(trace, {}),
             "device": {"kind": "TPU v5 lite"}}
    assert read(empty) is None
    red = mcmscopes.reduce_scopes(trace, {MODULE: TABLE})
    ctx = {"_scoped": red, "trace": devtrace.reduce_xplane(trace),
           "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 3 << 30},
           "least_bytes": least, "job_walls": [9e-6, 8e-6, 7e-6]}
    obs.enable(install_hooks=False)
    try:
        for _ in range(3):  # the warm-up job and two more
            obs.count("models.mcm.jobs")
            obs.count("models.mcm.phases", 6)
            for mode, steps in (("push", 80), ("pull", 4)):
                obs.count("models.mcm.init_steps", steps, mode=mode)
            for mode, steps in (("push", 24), ("pull", 0)):
                obs.count("models.mcm.layers", steps, mode=mode)
        value = read(ctx)
    finally:
        obs.disable()
        obs.reset()
    device_s = red["device_s"]
    flat = sorted(_phase_ns(p) + (COND if p is run[-1] else 0)
                  for run in PHASES for p in run)
    want = {
        "mcm_device_ms": 1e3 * device_s,
        "mcm_init_ms": (EDGE + ROUNDS * GRANT + 2 * WALK + SWEEP) * 1e-6,
        "mcm_phase_ms": flat[2] * 1e-6,
        "mcm_phases": 6.0,
        "mcm_push_share": 100 * 104 / 108,
        "mcm_host_gap_ms": 8e-3 - 1e3 * device_s,
        "mcm_hbm_share": 100 * (least / 819e9) / device_s,
        "mcm_hbm_peak_gb": (3 << 30) / 1e9,
    }[name]
    assert value == pytest.approx(want)
    if name.endswith("_share"):
        assert 0 < value < 100


# --- what the cell added ----------------------------------------------------


def test_the_cell_its_configuration_and_its_eight_readers_are_appended():
    spec = _spec()
    doc = spec.doc
    cells = [w["name"] for w in doc["workloads"]]
    configs = [c["name"] for c in doc["configs"]]
    # after the deep-graph cell, wherever later cells go: no place is pinned
    assert cells.index("rgg-n20.bfs-deep-sat") < cells.index(CELL)
    assert configs.index("rgg-n20-1x1") < configs.index(CONFIG)
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    reported = {m["name"] for m in spec.metrics_for(CELL, "end_to_end")}
    assert reported == {"mteps", "setup_s"}
    mteps = next(m for m in doc["end_to_end"] if m["name"] == "mteps")
    at = mteps["workloads"].index
    assert at("g500-sq15x4.spgemm-mesh") < at(CELL)
    # its own readers, in order, each in this cell alone
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index(READERS[0])
    assert names[first:first + len(READERS)] == READERS
    assert names.index("deep_hbm_share") < first
    for m in doc["per_layer"][first:first + len(READERS)]:
        assert m["workloads"] == [CELL] and m["moves"] == "mteps"
        assert m["layer"] == "algorithms + local kernels"
        assert spec.find(os.path.join("layers", m["name"] + ".py"))
    # and the per-layer metrics every cell reports
    mine = {m["name"] for m in spec.metrics_for(CELL, "per_layer")}
    assert {"compiles_in_window", "load_s", "warmup_s", "graph_ready_s",
            "upload_s", "boot_trace_s", "boot_fetch_s", "boot_probe_s",
            "boot_unspanned_s"} | set(READERS) == mine
    cfg = spec.config(CONFIG)
    entry = doc["configs"][configs.index(CONFIG)]
    assert cfg["source"].startswith("CombBLAS Applications/"
                                    "BipartiteMatchings/BPMaximumMatching")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["scale"] == list(cfg["reduced"])
    assert cfg["scale"] in (18, 19, 20) and cfg["kinds"] == []
    assert (cfg["edgefactor"], cfg["graph_seed"], cfg["grid"]) == (
        16, 1, [1, 1])
    assert cfg["law"] == "bipartite-rmat"
    assert cfg["rmat"]["symmetrised"] is False
    assert (cfg["rmat"]["a"], cfg["rmat"]["b"], cfg["rmat"]["c"],
            cfg["rmat"]["d"]) == (0.57, 0.19, 0.19, 0.05)
    assert {"edgefactor", "initialisation", "phases", "job", "parents",
            "paper_scale", "graph_seed"} <= set(cfg["assumed"])
    assert "draws NOTHING a job reads" in cfg["assumed"]["graph_seed"]
    assert {"maximum", "matching", "jobs", "window"} == set(
        cfg["guarantees"])
    mix = spec.traffic(MIX)
    assert mix["driver"] == "library_match"
    assert mix["entry"] == "combblas_tpu.models.matching:mcm_job"
    assert mix["check"] == {"sampled": 4}
    with open(os.path.join(CHECKOUT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) < 64 * 1024


def test_the_cell_and_its_control_through_the_real_command(tmp_path):
    bench = small_benchmark(str(tmp_path))
    # (a seed beyond 32 signed bits, as the driver's are)
    r, line = run_cell(bench, CELL, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"mteps", "setup_s"} and m["mteps"] > 0
    assert "against the reference (limits: equality)" in r.stderr
    assert "mcm: every job cardinality 340 in 4 phases" in r.stderr
    r, line = run_cell(bench, CELL, trace=1, seed=4, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    # the counters' readers on any platform, the device trace's only
    # where there is a device plane
    assert set(m) == {
        "load_s", "warmup_s", "compiles_in_window", "graph_ready_s",
        "upload_s", "boot_trace_s", "boot_fetch_s", "boot_probe_s",
        "boot_unspanned_s", "mcm_phases", "mcm_push_share"}
    assert m["compiles_in_window"] == 0 and m["mcm_phases"] == 4.0
    assert 0 < m["mcm_push_share"] <= 100
    # the control, in this process (the backend is the rehearsal's)
    for fault, rc in (("none", 0), ("undone", 0), ("stray", 0)):
        assert mcmcontrol.main(
            ["--seed", "2300001111", "--fault", fault, "--bench", bench]
        ) == rc
    out = mcmcontrol.control(Spec(bench), 7, "undone")
    assert out["correct"] is False and "the maximum is 340" in out[
        "problems"][0]
    out = mcmcontrol.control(Spec(bench), 7, "stray")
    assert out["correct"] is False and "no stored nonzero" in out[
        "problems"][0]
    assert mcmcontrol.control(Spec(bench), 7, "none")["correct"] is True
