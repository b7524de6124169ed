"""GAP's BC kernel's part of the benchmark without the chip: the plain
reference against hand-made graphs, the driver's sampler and its fast
failure on an answer without a depth, the six readers on a small trace
of a program with two loops and without one, the cost function by hand,
what the cell added to ``BENCHMARK.json`` (and what the kernel-3 cell
still holds there), and one rehearsal of ``g500-s20bc.bc-sat`` through
the real command at scale 9."""

import os

import numpy as np
import pytest

from chipbench import bccost, bcref, bcscopes, devtrace, scopes
from chipbench.spec import CHECKOUT, Spec

from rehearse import check_line, run_cell, small_benchmark

NS = 1e-9
CELL, CONFIG, MIX = "g500-s20bc.bc-sat", "g500-s20-bc-1x1", "bc-sat"
K3_CELL = "g500-s20k3.sssp-sat"
READERS = ["bc_device_ms", "bc_forward_ms", "bc_backward_ms",
           "bc_sweeps", "bc_gather_share", "bc_hbm_share"]
SHARED = {"sat_lane_fill", "execute_ms", "scatter_ms", "hbm_peak_gb",
          "launch_ms", "readback_ms", "to_global_ms",
          "readback_mb_per_query", "scatter_copied_mb", "batch_gap_ms"}


# --- the reference against hand-made graphs ---------------------------------

#   0 -- 1 -- 3 -- 4        5 -- 6        7
#   0 -- 2 -- 3
EDGES = [(0, 1), (0, 2), (1, 3), (2, 3), (3, 4), (5, 6)]
#: from 0: 3 lies on both paths to 4 and 1, 2 on one each of two to 3, 4
DELTA0 = np.array([0, 1, 1, 1, 0, 0, 0, 0.])


@pytest.fixture(scope="module")
def ref():
    r = np.array([e[0] for e in EDGES] + [e[1] for e in EDGES])
    c = np.array([e[1] for e in EDGES] + [e[0] for e in EDGES])
    order = np.argsort(r * 8 + c)
    return bcref.BCReference(8, r[order], c[order])


def test_reference_is_brandes_one_source_at_a_time(ref):
    assert np.array_equal(ref.dependencies(0), DELTA0)
    # from 4: everything else hangs on 3; 1 and 2 carry half of 0 each
    assert np.array_equal(ref.dependencies(4), [0, .5, .5, 3, 0, 0, 0, 0])
    assert not ref.dependencies(5).any() and not ref.dependencies(7).any()
    # the sum rule: 1 and 2 at distance 1, 3 at 2, 4 at 3
    assert ref.sum_rule(0) == 0 + 0 + 1 + 2 == DELTA0.sum()
    assert ref.sum_rule(4) == 4.0 and ref.sum_rule(7) == 0
    assert ref.level_count([0]) == 4 and ref.level_count([5, 7]) == 2
    assert ref.level_count([7]) == 1
    # all sources added up are the textbook's scores, counted from both
    # ends of every pair: 3 carries 0-4, 1-4, 2-4 and half of 1-2
    total = sum(ref.dependencies(s) for s in range(8))
    assert np.array_equal(total, [1, 2, 2, 7, 0, 0, 0, 0])


def test_checks_hold_a_float32_answer_and_name_what_is_wrong(ref):
    deg = ref.bfs.deg
    good = DELTA0.astype(np.float32)
    assert bcref.check_answer(good, 0, deg) is None
    assert ref.check_exact(good, 0) is None
    assert ref.check_sum(good, 0) is None
    assert ref.check_trial(2 * good, [0, 0]) is None
    # a rounding inside the tolerance passes, one outside does not
    assert ref.check_exact(good * np.float32(1 + bcref.RTOL / 4), 0) is None
    bad = ref.check_exact(good * np.float32(1 + 4 * bcref.RTOL), 0)
    assert bad.startswith("root 0: score[1]") and "3 entries" in bad
    # an entry the reference gives as 0 is 0 exactly
    off = good.copy()
    off[4] = 1e-30
    assert "score[4]" in ref.check_exact(off, 0)
    assert "sum rule says 3" in ref.check_sum(good * np.float32(1.001), 0)
    off = good.copy()
    off[6] = 0.5
    assert "vertex 6 is not reached" in ref.check_sum(off, 0)
    assert "trial 0 4" in ref.check_trial(good, [0, 4])
    assert ref.worst(good * np.float32(1.5), DELTA0) == pytest.approx(0.5)


# --- the driver ------------------------------------------------------------


def test_driver_ends_the_run_on_an_answer_without_its_depth():
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    assert spec.traffic(MIX)["driver"] == "serve_closed_bc"
    drv = spec.load_module("drivers", "serve_closed_bc")
    drv.require_scores({"scores": 0, "batch_niter": 7})
    with pytest.raises(SystemExit) as e:
        drv.require_scores({"scores": 0})  # the parent's answer
    assert "'scores' and 'batch_niter'" in str(e.value) and e.value.code != 0


def _sampler(drv, ref, requests, seed=2300001111):
    s = drv.BCSampler(seed, 4, 4, 8, ref.bfs.deg)
    for first in range(0, requests, 4):
        s.submitted(first)
    return s


def test_driver_keeps_one_whole_trial_and_a_sample(ref):
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    drv = spec.load_module("drivers", "serve_closed_bc")
    s = _sampler(drv, ref, 160)
    assert len(s.exact) == 4 and s.exact[0] % 4 == 0
    assert s.exact == list(range(s.exact[0], s.exact[0] + 4))
    assert len(set(s.sum)) == 8 and max(s.sum) < 160
    assert sorted(_sampler(drv, ref, 160).sum) == sorted(s.sum)
    good = {"scores": DELTA0.astype(np.float32), "batch_niter": 4}
    for i in range(160):
        s.take(i, 0, good)
    assert set(s.kept) == set(s.exact) | set(s.sum) and not s.problems
    # a kept answer no longer pins the batch it was a lane of
    assert all(k[1].base is None for k in s.kept.values())
    assert drv.check_sample(ref, s) == []
    # every answer gets the O(n) checks, sampled or not
    s.take(999, 1, good)
    assert "request 999: root 1 scores 1.0 itself" in s.problems[0]
    # a trial cut short by the drain, a wrong answer, a depth too small
    t = _sampler(drv, ref, 160)
    t.take(t.exact[0], 0, {"scores": 2 * good["scores"], "batch_niter": 4})
    shallow = next(i for i in t.sum if i not in t.exact)
    t.take(shallow, 0, {"scores": good["scores"], "batch_niter": 3})
    found = " | ".join(drv.check_sample(ref, t))
    assert "3 of the sampled trials' 4 answers did not complete" in found
    assert "reference says 1.0" in found
    assert "reported 3 levels, the root alone has 4" in found
    with pytest.raises(SystemExit):  # check.exact counts whole trials
        drv.BCSampler(1, 4, 6, 8, ref.bfs.deg)


def test_the_sample_is_drawn_over_all_the_run_sent(ref):
    """Nobody knows beforehand how many requests a window holds: the
    sample is a reservoir filled at submission, so every request the run
    sent, the last batch's too, is as likely checked as the first; an
    answer whose place a later request took is dropped."""
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    drv = spec.load_module("drivers", "serve_closed_bc")
    good = {"scores": DELTA0.astype(np.float32), "batch_niter": 4}
    trials, sums = np.zeros(40), np.zeros(160)
    for seed in range(400):
        s = drv.BCSampler(seed, 4, 4, 8, ref.bfs.deg)
        for first in range(0, 160, 4):
            s.submitted(first)
            for i in range(first, first + 4):  # answered at once
                s.take(i, 0, good)
            assert set(s.kept) == set(s.exact) | set(s.sum)
            assert len(s.kept) <= 12
        trials[s.exact[0] // 4] += 1
        sums[s.sum] += 1
    # each quarter of the run holds its share of the sample (400 trials:
    # 100 a quarter, sd 8.7; 3200 requests: 800 a quarter, sd 24)
    assert all(60 < q < 140 for q in trials.reshape(4, -1).sum(1))
    assert all(700 < q < 900 for q in sums.reshape(4, -1).sum(1))


def test_the_control_of_the_precision_through_the_cell_s_own_checks(tmp_path):
    """``python3 -m chipbench.bccontrol``: answers held in bfloat16 come
    out NOT correct through the driver's ``check_sample``, by the limit
    on a score (``RTOL``) on every answer of the trial; float32 in
    numpy's row order comes out correct."""
    import json
    import subprocess
    import sys

    bench = small_benchmark(str(tmp_path), scale=10)

    def control(held_in):
        r = subprocess.run(
            [sys.executable, "-m", "chipbench.bccontrol", "--bench", bench,
             "--seed", "2300001111", "--held-in", held_in],
            cwd=CHECKOUT, capture_output=True, text=True, timeout=300)
        return r, json.loads(r.stdout.strip().splitlines()[-1])

    r, out = control("bfloat16")
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is False and out["checked"] >= 8
    # four answers and their sum: the limit that separates the precisions
    assert out["refused_by"]["RTOL"] == 5
    assert "reference says" in out["problems"][0]
    r, out = control("float32")
    assert r.returncode == 0, r.stderr[-2000:]
    assert out["correct"] is True and out["problems"] == []
    assert out["refused_by"] == {"RTOL": 0, "RTOL_SUM": 0}


# --- the readers -----------------------------------------------------------

MODULE = "jit_serve_bc_w16"
_PATH = "jit(serve_bc_w16)/"
_FWD = _PATH + "bc.forward/while/body/jit(dist_spmv_ell_multi)/"
_BWD = _PATH + "bc.backward/while/body/jit(dist_spmv_ell_multi)/"
#: what ``combblas_tpu.obs.opnames`` would hold for the program
TABLE = {
    "fusion.9": _PATH + "bc.init/jit(_where)/select_n",
    "while.5": _PATH + "bc.forward/while",
    "compare.7": _PATH + "bc.forward/while/cond/lt",
    "fusion.1": _FWD + "ell.bucket0/gather/gather",
    "fusion.2": _FWD + "ell.bucket0/fold/reduce_sum",
    "while.6": _PATH + "bc.backward/while",
    "compare.8": _PATH + "bc.backward/while/cond/lt",
    "fusion.3": _BWD + "ell.bucket0/gather/gather",
    "fusion.4": _BWD + "ell.bucket0/fold/reduce_sum",
    "fusion.10": _PATH + "bc.finish/jit(_where)/select_n",
}
_NAMES = ["fusion.9", "while.5", "compare.7", "fusion.1", "fusion.2",
          "while.6", "compare.8", "fusion.3", "fusion.4", "fusion.10",
          "copy.11", MODULE + "(5)"]
_ID = {name: i + 1 for i, name in enumerate(_NAMES)}
#: gather ns of each sweep of the two whole executions; fold 500, the
#: condition 10, and 90 ns of the loop's own after its last condition
FORWARD = [[1490, 2490, 390], [1490, 390, 390, 390]]
BACKWARD = [[1490, 1390], [2390, 390, 390]]
FOLD, COND, TAIL, EDGE = 500, 10, 90, 500


def _ev(name: str, start: int, end: int) -> str:
    return (f"events {{ metadata_id: {_ID[name]} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} }}")


def _loop(name, cond, gather, fold, t0, gathers):
    """One loop from ``t0``: its events and where it ends."""
    evs, t = [], t0
    for g in gathers:
        evs += [_ev(cond, t, t + COND), _ev(gather, t + COND, t + COND + g),
                _ev(fold, t + COND + g, t + COND + g + FOLD)]
        t += COND + g + FOLD
    evs.append(_ev(cond, t, t + COND))
    end = t + COND + TAIL
    return [_ev(name, t0, end)] + evs, end


def _execution(t0, forward, backward):
    init = [_ev("fusion.9", t0, t0 + EDGE)]
    fwd, t = _loop("while.5", "compare.7", "fusion.1", "fusion.2",
                   t0 + EDGE, forward)
    bwd, t = _loop("while.6", "compare.8", "fusion.3", "fusion.4", t,
                   backward)
    return (init + fwd + bwd + [_ev("fusion.10", t, t + EDGE)],
            (t0, t + EDGE))


def _trace() -> bytes:
    from jax.profiler import ProfileData

    ops, mods, t = [_ev("copy.11", 100, 200)], [], 1000
    for fw, bw in zip(FORWARD, BACKWARD):
        evs, span = _execution(t, fw, bw)
        ops += evs
        mods.append(_ev(MODULE + "(5)", *span))
        t = span[1] + 1000
    # a third execution, cut by the trace's end
    ops.append(_ev("fusion.9", t, t + 1000))
    mods.append(_ev(MODULE + "(5)", t, t + 1000))
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: '
        f'"%{n} = f32[64,16]{{0,1}} fusion(%p)" }} }}'
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


def _sweep(g):
    return COND + g + FOLD


def test_scopes_of_this_kind_on_a_trace_with_two_loops():
    red = bcscopes.reduce_scopes(_trace(), {MODULE: TABLE})
    by = red["by_scope"]
    assert red["module"] == MODULE and red["executions"] == 2
    assert not any(k.startswith("bfs.") for k in by)
    assert set(by) == {
        "bc.init", "bc.forward", "bc.backward", "bc.finish",
        "bc.forward/ell.bucket0/gather", "bc.forward/ell.bucket0/fold",
        "bc.backward/ell.bucket0/gather", "bc.backward/ell.bucket0/fold"}
    assert by["bc.init"] == pytest.approx(EDGE * NS)
    assert by["bc.finish"] == pytest.approx(EDGE * NS)
    assert by["bc.forward/ell.bucket0/gather"] == pytest.approx(
        sum(map(sum, FORWARD)) / 2 * NS)
    assert by["bc.backward/ell.bucket0/fold"] == pytest.approx(
        sum(map(len, BACKWARD)) / 2 * FOLD * NS)
    # the loop's own: its conditions and the tail
    assert by["bc.forward"] == pytest.approx(
        (sum(len(f) + 1 for f in FORWARD) * COND + 2 * TAIL) / 2 * NS)
    assert red["unscoped_s"] == 0
    assert sum(by.values()) == pytest.approx(red["device_s"])
    # the last sweep of a loop runs to the loop's end
    want = [[_sweep(g) for g in run[:-1]] + [_sweep(run[-1]) + COND + TAIL]
            for run in FORWARD]
    assert [[round(s / NS) for s in lv] for lv in red["levels"]] == want
    want = [[_sweep(g) for g in run[:-1]] + [_sweep(run[-1]) + COND + TAIL]
            for run in BACKWARD]
    assert [[round(s / NS) for s in lv] for lv in red["backward"]] == want
    ctx = {"_scoped": red}
    assert bcscopes.sweep_ms(ctx, "forward") == pytest.approx(
        1000 * 1e-6)  # median of 2000 3000 1000 2000 900 900 1000
    assert bcscopes.sweep_ms(ctx, "backward") == pytest.approx(2000e-6)
    assert bcscopes.sweeps_run(ctx) == (3.5, 2.5)
    # the same trace under no table, or BFS's, holds nothing of this kind
    bare = bcscopes.reduce_scopes(_trace(), {})
    assert bare["by_scope"] is None and bare["backward"] is None
    assert bcscopes.sweep_ms({"_scoped": bare}, "forward") is None
    assert bcscopes.sweeps_run({"_scoped": bare}) is None
    # and scopes.py's own reading of a BFS trace is what it was
    import tiny_scoped_trace as T

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "tiny_scoped.xplane.pb")
    assert "bfs.level/ell.bucket0/gather" in scopes.reduce_scopes(
        path, {T.MODULE: T.TABLE})["by_scope"]


def test_least_bytes_of_a_batch_by_hand():
    n, slots, width = 1 << 20, 36_953_104, 16
    # index + f32 one a slot; the [n, 16] f32 table in, the result out
    assert bccost.bc_sweep_least_bytes(slots, n, width) == (
        295_624_832 + 134_217_728) == 429_842_560
    assert bccost.bc_batch_least_bytes(slots, n, width, 7, 6) == (
        13 * 429_842_560)
    assert bccost.bc_batch_least_bytes(96, 64, 16, 3.5, 2.5) == (
        6 * (8 * 96 + 8 * 64 * 16))


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_on_a_trace_and_none_without(name):
    from combblas_tpu import obs

    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    read = spec.load_module("layers", name).read
    obs.reset()
    # nothing traced, no counter, a program without these scopes: None
    assert read({"device": {"kind": "TPU v5 lite"}}) is None
    trace = _trace()
    empty = {"_scoped": bcscopes.reduce_scopes(trace, {}),
             "device": {"kind": "TPU v5 lite"},
             "bc_cost": {"n": 64, "slots": 96, "width": 16}}
    if name != "bc_device_ms":
        assert read(empty) is None
    red = bcscopes.reduce_scopes(trace, {MODULE: TABLE})
    ctx = {
        "_scoped": red, "trace": devtrace.reduce_xplane(trace),
        "device": {"kind": "TPU v5 lite"},
        "bc_cost": {"n": 64, "slots": 96, "width": 16},
    }
    obs.enable(install_hooks=False)
    try:
        # (other batches than the slice's two executions: the share of
        # the roofline reads the trace's sweeps, not the counter's)
        for forward in (7, 8):
            obs.count("serve.bc.sweeps", forward, phase="forward", width=16)
            obs.count("serve.bc.sweeps", forward - 1, phase="backward",
                      width=16)
            obs.count("serve.bc.batches", 1, width=16)
        value = read(ctx)
    finally:
        obs.disable()
        obs.reset()
    device_s = red["device_s"]
    leaves = (sum(map(sum, FORWARD + BACKWARD))
              + FOLD * sum(map(len, FORWARD + BACKWARD))) / 2 * NS
    want = {
        "bc_device_ms": 1e3 * device_s, "bc_forward_ms": 1000e-6,
        "bc_backward_ms": 2000e-6, "bc_sweeps": 14.0,
        "bc_gather_share": 100 * leaves / device_s,
        "bc_hbm_share": 100 * (bccost.bc_batch_least_bytes(
            96, 64, 16, 3.5, 2.5) / 819e9) / device_s,
    }[name]
    assert value == pytest.approx(want)
    if name == "bc_hbm_share":
        assert 0 < value < 100


# --- what the cell added, and what the cell before it still holds -----------


def test_the_cell_is_appended_and_its_readers_wait_for_a_benchmark_pr():
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert not set(READERS) & set(names)
    drv = spec.load_module("drivers", "serve_closed_bc")
    assert list(drv.LAYERS) == READERS
    # after the kernel-3 cell, wherever later cells go: no place is pinned
    cells = [w["name"] for w in spec.doc["workloads"]]
    configs = [c["name"] for c in spec.doc["configs"]]
    assert cells.index(K3_CELL) < cells.index(CELL)
    assert configs.index("g500-s20-k3-1x1") < configs.index(CONFIG)
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    reported = {m["name"] for m in spec.metrics_for(CELL, "end_to_end")}
    assert reported == {"qps", "setup_s"}
    mine = {m["name"] for m in spec.metrics_for(CELL, "per_layer")}
    assert SHARED <= mine
    assert not any(m.startswith(("bfs_", "k2_")) for m in mine)
    # it joined the eleven lists the kernel-3 cell is in, after it
    joined = [m for sec in ("end_to_end", "per_layer")
              for m in spec.doc[sec] if CELL in m.get("workloads", ())]
    assert {m["name"] for m in joined} == SHARED | {"qps"}
    for m in joined:
        at = m["workloads"].index
        assert at(K3_CELL) < at(CELL), m["name"]
    cfg = spec.config(CONFIG)
    assert list(cfg["reduced"]) == ["scale"] and cfg["kinds"] == ["bc"]
    assert (cfg["scale"], cfg["edgefactor"], cfg["graph_seed"]) == (20, 16, 1)
    assert cfg["lane_widths"] == [16] and cfg["keep_coo"] is True
    assert cfg["source"] == spec.doc["configs"][
        configs.index(CONFIG)]["source"]
    assert {"request", "graph_seed", "lane_widths"} <= set(cfg["assumed"])
    assert {"scores", "sum_rule", "every_answer", "batches"} == set(
        cfg["guarantees"])
    # the graph is g500-s20-1x1's: same generator, same seed
    base = spec.config("g500-s20-1x1")
    assert all(cfg[k] == base[k] for k in (
        "grid", "scale", "edgefactor", "graph_seed", "rmat"))
    mix = spec.traffic(MIX)
    assert (mix["kind"], mix["in_flight"]) == ("bc", 32)
    # GAP's four roots a trial shape the traffic; the check counts answers
    assert mix["trial"] == 4 and mix["check"] == {"exact": 4, "sum": 8}


def test_the_kernel_3_cell_keeps_all_but_the_last_place():
    """``test_chipbench_k3.py:244-245`` asserts the kernel-3 cell and its
    configuration are the LAST of ``BENCHMARK.json``, which no later cell
    can leave true (the driver takes new entries only at the end) and
    which only a ``benchmark`` PR may edit.  That case FAILS from this PR
    on, at line 244, and is left failing in the open (``PERF.md`` section
    7); everything else it holds is held here, with no place pinned."""
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    k3 = ["sssp_device_ms", "sssp_round_ms", "sssp_parents_ms",
          "sssp_rounds", "sssp_gather_share", "sssp_hbm_share"]
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert not set(k3) & set(names)
    drv = spec.load_module("drivers", "serve_closed_k3")
    assert list(drv.LAYERS) == k3
    assert K3_CELL in [w["name"] for w in spec.doc["workloads"]]
    assert "g500-s20-k3-1x1" in [c["name"] for c in spec.doc["configs"]]
    cell = spec.cell(K3_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "g500-s20-k3-1x1", "sssp-sat", 1)
    reported = {m["name"] for m in spec.metrics_for(K3_CELL, "end_to_end")}
    assert reported == {"qps", "setup_s"}
    mine = {m["name"] for m in spec.metrics_for(K3_CELL, "per_layer")}
    assert SHARED <= mine
    assert not any(m.startswith(("bfs_", "k2_")) for m in mine)
    cfg = spec.config("g500-s20-k3-1x1")
    assert list(cfg["reduced"]) == ["scale"] and cfg["kinds"] == ["sssp"]
    assert (cfg["scale"], cfg["edgefactor"], cfg["graph_seed"]) == (20, 16, 1)
    mix = spec.traffic("sssp-sat")
    assert (mix["kind"], mix["in_flight"]) == ("sssp", 32)
    assert mix["check"] == {"exact": 2, "tree": 4}


# --- the cell, rehearsed ----------------------------------------------------


def test_the_cell_through_the_real_command(tmp_path):
    bench = small_benchmark(str(tmp_path))
    # (a seed beyond 32 signed bits, as the driver's are)
    r, line = run_cell(bench, CELL, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"qps", "setup_s"} and m["qps"] > 0
    assert line["attempted"] % 4 == 0  # whole trials
    assert "bc: checked" in r.stderr and "sampled answers" in r.stderr
    assert "largest relative error against float64" in r.stderr
    r, line = run_cell(bench, CELL, trace=1, seed=4, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"deployment {CONFIG}: snapshot" in r.stderr
    m = check_line(line)
    assert (SHARED - {"hbm_peak_gb"}) | {
        "load_s", "warmup_s", "compiles_in_window"} <= set(m)
    assert m["compiles_in_window"] == 0 and m["sat_lane_fill"] > 90
    # the kind's own readings are logged, not in the line: the counter's
    # on any platform, the device trace's only where there is a device plane
    logged = dict(ln.split("layer ", 1)[1].split(": ", 1)
                  for ln in r.stderr.splitlines() if "layer bc_" in ln)
    assert list(logged) == READERS and not set(READERS) & set(m)
    # forward sweeps and one fewer backward: an odd count a batch
    assert 3 <= float(logged.pop("bc_sweeps")) <= 41
    assert set(logged.values()) == {"nothing to read"}
    # one [n, 16] block of four bytes a batch, over its 16 requests (a
    # little more where the drain's last batch was not full)
    assert m["readback_mb_per_query"] == pytest.approx(
        4 * 512 / 1e6, rel=0.05)
