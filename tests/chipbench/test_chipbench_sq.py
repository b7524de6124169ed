"""Upstream's MultTime product's part of the benchmark without the chip:
the plain reference against the definition and a digest by hand, the
driver's two checks each tripped by one planted wrong entry and its fast
exit on a program without the entry, the control's bfloat16 accumulator,
the cost function by hand, the seven readers on a small trace of a job
of several programs (and without the job's annotation or its tables),
what the cell added to ``BENCHMARK.json`` (order checks, no place
pinned), and one rehearsal of ``g500-sq.spgemm-batch`` through the real
command at scale 8."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import (devtrace, graph, sqcontrol, sqcost, sqref,
                       sqscopes)
from chipbench.spec import CHECKOUT, Spec

from rehearse import check_line, run_cell, small_benchmark

NS = 1e-9
CELL, CONFIG, MIX = "g500-sq.spgemm-batch", "g500-sq-1x1", "spgemm-batch"
TC_CELL, TC_CONFIG = "g500-s18tc.tc-batch", "g500-s18-tc-1x1"
READERS = ["sq_device_ms", "sq_dot_ms", "sq_extract_ms", "sq_host_gap_ms",
           "sq_mnnz_out_per_s", "sq_hbm_share", "sq_hbm_peak_gb"]


def _spec():
    return Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))


# --- the reference ----------------------------------------------------------

#   0 - 1 - 2 - 0 (a triangle), 2 - 3, 4 alone
EDGES = [(0, 1), (1, 2), (2, 0), (2, 3)]


def _coo(edges):
    r = np.array([e[0] for e in edges] + [e[1] for e in edges], np.int32)
    c = np.array([e[1] for e in edges] + [e[0] for e in edges], np.int32)
    return r, c


def _dense_square(n, rows, cols):
    a = np.zeros((n, n), np.int64)
    np.add.at(a, (rows, cols), 1)
    return a @ a


def test_reference_is_the_square_and_its_digest_by_hand():
    ref = sqref.SQReference(5, *_coo(EDGES))
    want = np.array([[2, 1, 1, 1, 0], [1, 2, 1, 1, 0], [1, 1, 3, 0, 0],
                     [1, 1, 0, 1, 0], [0, 0, 0, 0, 0]])
    assert np.array_equal(ref.C.toarray(), want)
    assert (ref.nnz_a, ref.products, ref.largest) == (8, 18, 3)
    d = ref.digest
    assert (d["nnz"], d["sum"]) == (14, 18)
    assert d["counts"].tolist() == [4, 4, 3, 3, 0]
    assert d["sums"].tolist() == [5, 5, 5, 3, 0]
    h = [(j + 1) * 0x9E3779B1 % 2**32 for j in range(5)]
    prints = [sum(int(want[i, j]) * h[j] for j in range(5)) % 2**32
              for i in range(5)]
    assert d["prints"].view(np.uint32).tolist() == prints
    assert all(d[k].dtype == np.int32 for k in ("counts", "sums", "prints"))
    # the hash is injective on columns: an entry moved along its row
    # changes the fingerprint
    assert len(set(h)) == 5 and sqref.MULTIPLIER % 2 == 1


@pytest.mark.parametrize("scale,seed", [(6, 1), (7, 2), (8, 1), (9, 3)])
def test_reference_equals_the_definition_on_the_generator_s_graphs(
        scale, seed):
    n, rows, cols, _ = graph.rmat_graph(scale, 16, seed)
    ref = sqref.SQReference(n, rows, cols)
    dense = _dense_square(n, rows, cols)
    assert np.array_equal(ref.C.toarray(), dense)
    assert ref.digest["nnz"] == np.count_nonzero(dense)
    assert ref.digest["sum"] == dense.sum() == ref.products
    # a diagonal entry is a degree, and the largest entry is one
    assert ref.largest == graph.degrees(rows, n).max()
    coo = ref.C.tocoo()
    assert ref.check_entries(coo.row, coo.col, coo.data.astype(
        np.float32)) is None
    assert ref.check_digest(ref.digest) is None


def test_checks_refuse_what_no_tolerance_would_let_by():
    ref = sqref.SQReference(5, *_coo(EDGES))
    coo = ref.C.tocoo()
    r, c, v = coo.row, coo.col, coo.data.astype(np.float32)
    off = v.copy()
    off[3] += 1
    assert ref.check_entries(r, c, off) == (
        "1 of 14 entries hold another value, first (0, 3): 2, the "
        "reference's 1")
    assert ref.check_entries(r[1:], c[1:], v[1:]).startswith(
        "13 entries, the reference has 14; 1 coordinates are in one and "
        "not the other, first (0, 0)")
    assert ref.check_entries(r, c, v + 0.5) == (
        "a stored value is not an integer")
    assert ref.check_entries(
        np.append(r, 0), np.append(c, 0), np.append(v, 0)) == (
        "1 stored tuples repeat a coordinate")
    # any order of the same tuples is the same matrix
    assert ref.check_entries(r[::-1], c[::-1], v[::-1]) is None
    d = dict(ref.digest)
    assert "nnz 13, the reference's is 14 (off by -1)" in ref.check_digest(
        dict(d, nnz=13))
    assert "sum 18.0 is not an integer" in ref.check_digest(
        dict(d, sum=18.0))
    assert "counts is int64[5], not int32[5]" in ref.check_digest(
        dict(d, counts=d["counts"].astype(np.int64)))
    flipped = d["prints"].copy()
    flipped[2] ^= 1
    assert ref.check_digest(dict(d, prints=flipped)).startswith(
        "prints differs in 1 rows, first row 2")


# --- the driver ------------------------------------------------------------


def test_driver_holds_digests_and_the_last_c_to_the_reference():
    spec = _spec()
    assert spec.traffic(MIX)["driver"] == "library_product"
    drv = spec.load_module("drivers", "library_product")
    picker = spec.load_module("drivers", "library_job").checked_jobs
    n, rows, cols, _ = graph.rmat_graph(7, 16, 1)
    ref = sqref.SQReference(n, rows, cols)
    coo = ref.C.tocoo()
    last = (coo.row, coo.col, coo.data.astype(np.float32))
    good = [dict(ref.digest) for _ in range(9)]
    picks = picker(7, 9, 4)
    assert picks[0] == 0 and picks[-1] == 8 and len(picks) == 6
    assert drv.check_jobs(ref, good, picks, last) == []
    assert drv.LEAST_JOBS == 4
    # ONE wrong entry planted in the C read back: the digests are the
    # reference's, the whole-C check alone finds it
    planted = last[2].copy()
    planted[len(planted) // 2] += 1
    found = drv.check_jobs(ref, good, picks, (last[0], last[1], planted))
    assert len(found) == 1 and found[0].startswith(
        "the last job's C: 1 of ") and "hold another value" in found[0]
    # the same wrong entry in what a job outside the sample digested:
    # its digest is not the first's
    c = ref.C.copy()
    c.data = c.data.copy()
    c.data[len(c.data) // 2] += 1
    wrong = sqref.digest_of(c)
    quiet = next(k for k in range(9) if k not in picks)
    jobs = list(good)
    jobs[quiet] = wrong
    assert drv.check_jobs(ref, jobs, picks, last) == [
        f"job {quiet}: its digest is not the first job's"]
    # every job wrong alike: the sample holds them to the reference
    found = drv.check_jobs(ref, [wrong] * 9, picks, last)
    assert len(found) == 6 and all(
        "sums differs in 1 rows" in f and "prints differs in 1 rows" in f
        for f in found)


def test_driver_ends_the_run_at_once_on_a_program_without_the_entry():
    """The parent of the PR that added ``spgemm_job``: the run ends
    before the graph is loaded, non-zero, with a sentence."""
    drv = _spec().load_module("drivers", "library_product")

    class Job:
        mix = {"entry": "combblas_tpu.parallel.spgemm:no_such_entry",
               "semiring": "combblas_tpu.semiring:PLUS_TIMES"}

        def deploy(self):
            raise AssertionError("the graph was loaded first")

    with pytest.raises(SystemExit) as e:
        drv.run(Job())
    assert "no 'combblas_tpu.parallel.spgemm:no_such_entry'" in str(e.value)
    assert e.value.code != 0


def test_the_control_refuses_a_bfloat16_accumulator(tmp_path):
    """``python3 -m chipbench.sqcontrol``: through the driver's own
    ``check_jobs``, the product rounded once to bfloat16 and the one a
    bfloat16 accumulator stalls at 256 come out NOT correct; held in
    float32 it comes out correct."""
    assert sqcontrol.round_to_bfloat16(
        np.array([0, 1, 255, 256, 257, 258, 259, 1023, 1025, 9705])
    ).tolist() == [0, 1, 255, 256, 256, 258, 260, 1024, 1024, 9728]
    bench = small_benchmark(str(tmp_path), scale=10)
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.sqcontrol", "--bench", bench,
         "--seed", "2300001111"],
        cwd=CHECKOUT, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = {o["held_in"]: o for o in map(
        json.loads, r.stdout.strip().splitlines())}
    assert list(out) == ["float32", "bfloat16", "bfloat16_stalled"]
    exact = out["float32"]
    assert exact["correct"] is True and exact["differing_entries"] == 0
    assert exact["largest"] > 256
    n, rows, cols, _ = graph.rmat_graph(10, 16, 1)
    data = sqref.SQReference(n, rows, cols).C.data
    once, stalled = out["bfloat16"], out["bfloat16_stalled"]
    assert once["correct"] is False and stalled["correct"] is False
    assert once["differing_entries"] == int(
        (sqcontrol.round_to_bfloat16(data) != data).sum()) > 0
    assert stalled["differing_entries"] == int((data > 256).sum()) >= once[
        "differing_entries"]
    assert any("hold another value" in p for p in once["problems"])
    # one precision by name; the exit code says whether the check held
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.sqcontrol", "--bench", bench,
         "--seed", "7", "--held-in", "bfloat16"],
        cwd=CHECKOUT, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and len(r.stdout.strip().splitlines()) == 1


# --- the cost ---------------------------------------------------------------


def test_least_bytes_of_a_job_by_hand():
    # scale 14: A's 426,544 nonzeros read twice, C's 40,668,600 written
    assert sqcost.sq_job_least_bytes(426_544, 40_668_600) == 12 * (
        853_088 + 40_668_600) == 498_260_256
    assert sqcost.sq_job_least_bytes(8, 14) == 360
    # 5.6e14 flop in 4 s of device time is 71% of the matrix unit
    assert sqcost.dense_flop_share(5.6e14, 4.0, 197.0) == pytest.approx(
        71.0659898)


# --- the readers -----------------------------------------------------------

FLOPS, BLOCK, CAT, DIGEST = (
    "jit_summa_stage_flops", "jit__windowed_block_local_dot",
    "jit_concatenate", "jit_spgemm_digest")
_P = "jit(f)/jit(main)/"
#: what ``combblas_tpu.obs.opnames`` would hold: a row block's two
#: launches are two programs of one module name, whose instruction
#: numbers mean different things
TABLES = {
    FLOPS: {"fusion.1": _P + "sq.symbolic/reduce_sum"},
    BLOCK + "#0": {"fusion.1": _P + "sq.densify/scatter-add",
                   "fusion.2": _P + "sq.dot/dot_general",
                   "while.3": _P + "sq.extract/while",
                   "fusion.5": _P + "concatenate"},
    BLOCK + "#1": {"fusion.1": _P + "sq.dot/dot_general",
                   "fusion.2": _P + "sq.densify/scatter-add",
                   "while.3": _P + "sq.extract/while",
                   "fusion.5": _P + "concatenate"},
    DIGEST: {"fusion.1": _P + "shard_map/sq.digest/scatter-add"},
}
SYM, DENS, DOT, BODY, TAIL, CATS, DIG = 100, 300, 2000, 1500, 50, 40, 200
GAP = 500  # the host between two programs
_OPS = ["fusion.1", "fusion.2", "while.3", "fusion.4", "fusion.5",
        "copy.9"]
_MODS = [FLOPS + "(1)", BLOCK + "(2)", BLOCK + "(3)", CAT + "(4)",
         DIGEST + "(5)"]
_ID = {name: i + 1 for i, name in enumerate(_OPS + _MODS + ["spgemm.job",
                                                            "numeric"])}


def _ev(name, start, end):
    return (f"events {{ metadata_id: {_ID[name]} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} }}")


def _job(t0, slow=0):
    """One job's device events from ``t0``: ``(ops, modules, end)``.
    ``slow`` lengthens the first block's product."""
    ops, mods, t = [], [], t0 + GAP

    def program(mod, steps):
        nonlocal t
        start = t
        for name, ns in steps:
            if name == "while.3":  # the loop holds one unnamed body op
                ops.append(_ev("while.3", t, t + ns))
                ops.append(_ev("fusion.4", t + 10, t + ns - 10))
            else:
                ops.append(_ev(name, t, t + ns))
            t += ns
        mods.append(_ev(mod, start, t))
        t += GAP

    program(_MODS[0], [("fusion.1", SYM)])
    program(_MODS[1], [("fusion.1", DENS), ("fusion.2", DOT + slow),
                       ("while.3", BODY + 20), ("fusion.5", TAIL)])
    program(_MODS[2], [("fusion.1", DOT), ("fusion.2", DENS),
                       ("while.3", BODY + 20), ("fusion.5", TAIL)])
    program(_MODS[3], [("copy.9", CATS)])
    program(_MODS[4], [("fusion.1", DIG)])
    return ops, mods, t


def _trace(annotated=True, early=0) -> bytes:
    """``early``: the second job's annotation opens that many ns AFTER
    its first program started on the device (the planes' skew)."""
    from jax.profiler import ProfileData

    ops, mods, host = [_ev("copy.9", 100, 200)], [], []
    t = 1000
    for k, slow in enumerate((0, 400)):
        o, m, end = _job(t, slow)
        ops += o
        mods += m
        opens = t + (GAP + early if k and early else 0)
        host.append(_ev("spgemm.job", opens, end))
        host.append(_ev("numeric", t + 700, end - 900))
        t = end + 100
    # a third job, cut by the trace's end: its annotation outlasts the
    # last device operation
    o, m, end = _job(t)
    ops += o[:3]
    mods += m[:1]
    host.append(_ev("spgemm.job", t, end))
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: '
        + (f'"%{n} = f32[64]{{0}} fusion(%p)"' if n in _OPS else f'"{n}"')
        + " } }" for n, i in _ID.items())

    def plane(pid, name, lines):
        body = " ".join(
            f'lines {{ id: {k + 1} name: "{nm}" timestamp_ns: 0 '
            + " ".join(evs) + " }" for k, (nm, evs) in enumerate(lines))
        return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'

    return ProfileData.text_proto_to_serialized_xspace(
        plane(1, "/device:TPU:0", (("XLA Modules", mods), ("XLA Ops", ops)))
        + " " + plane(2, "/host:CPU", (
            ("python3", host if annotated else []),)))


#: device nanoseconds of one job, by hand (the mean of the two whole)
_BLOCKS = 2 * (DENS + DOT + BODY + 20 + TAIL)
DEVICE = SYM + _BLOCKS + CATS + DIG + 400 / 2


@pytest.fixture
def no_slack(monkeypatch):
    """The hand-made trace's jobs are microseconds long: the millisecond
    a job may reach back before its annotation would take in the stray
    operation that opens the trace."""
    monkeypatch.setattr(sqscopes, "ALIGN_S", 0.0)


def test_a_job_of_several_programs_reduced_by_its_own_annotation(no_slack):
    red = sqscopes.reduce_jobs(_trace(), TABLES)
    assert red["jobs"] == 2
    assert red["device_s"] == pytest.approx(DEVICE * NS)
    # the annotation's length: the device's time and six host gaps
    assert red["wall_s"] == pytest.approx((DEVICE + 6 * GAP) * NS)
    by = red["by_scope"]
    assert set(by) == {"sq.symbolic", "sq.densify", "sq.dot", "sq.extract",
                       "sq.digest"}
    assert by["sq.symbolic"] == pytest.approx(SYM * NS)
    assert by["sq.digest"] == pytest.approx(DIG * NS)
    # the second launch's table, not the first's: fusion.1 is its product
    assert by["sq.densify"] == pytest.approx(2 * DENS * NS)
    assert by["sq.dot"] == pytest.approx((2 * DOT + 200) * NS)
    # the loop's unnamed body takes the loop's scope
    assert by["sq.extract"] == pytest.approx(2 * (BODY + 20) * NS)
    # a program with no table, and an instruction with no scope
    assert red["unscoped_s"] == pytest.approx((CATS + 2 * TAIL) * NS)
    assert sum(by.values()) + red["unscoped_s"] == pytest.approx(
        red["device_s"])
    assert red["modules"][BLOCK] == [2, pytest.approx((_BLOCKS + 200) * NS)]
    assert red["modules"][CAT] == [1, pytest.approx(CATS * NS)]
    assert sqscopes.label(_P + "sq.extract/while/body/gather") == (
        "sq.extract")
    assert sqscopes.label("jit(f)/bfs.level/gather") is None
    assert sqscopes.label(None) is None
    ctx = {"_sq_scoped": red}
    assert sqscopes.scope_ms(ctx, ("sq.densify", "sq.dot")) == pytest.approx(
        (2 * DENS + 2 * DOT + 200) * 1e-6)
    assert sqscopes.scope_ms(ctx, ("tc.harvest",)) is None
    # under no table the job is still timed, and holds no scope
    bare = sqscopes.reduce_jobs(_trace(), {})
    assert bare["by_scope"] is None
    assert bare["device_s"] == pytest.approx(DEVICE * NS)
    assert sqscopes.scope_ms({"_sq_scoped": bare}, ("sq.dot",)) is None
    # a program that writes no annotation (the parent) has no job
    assert sqscopes.reduce_jobs(_trace(annotated=False), TABLES) is None


def test_a_job_takes_what_ran_since_the_job_before_closed(monkeypatch):
    """The planes of a trace agree to about a millisecond: a second
    job's first program that starts, on the device's clock, before its
    annotation opens is that job's; the first whole job reaches back
    ``ALIGN_S``."""
    exact = sqscopes.reduce_jobs(_trace(early=300), TABLES)
    # job 2's counting pass started 300 ns before its annotation, after
    # job 1 closed; the stray operation is inside job 1's reach
    assert exact["device_s"] == pytest.approx((DEVICE + 100 / 2) * NS)
    assert exact["by_scope"]["sq.symbolic"] == pytest.approx(SYM * NS)
    assert exact["modules"][FLOPS][0] == 1
    monkeypatch.setattr(sqscopes, "ALIGN_S", 0.0)
    assert sqscopes.reduce_jobs(_trace(early=300), TABLES)[
        "device_s"] == pytest.approx(DEVICE * NS)


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_on_a_trace_and_none_without(name, no_slack):
    from combblas_tpu import obs

    read = _spec().load_module("layers", name).read
    obs.reset()
    # nothing traced, no counter, no peak, no job: None, never 0 and
    # never an exception
    assert read({"device": {"kind": "TPU v5 lite"}}) is None
    assert read({"_sq_scoped": None, "job_walls": [],
                 "device": {"kind": "TPU v5 lite"}}) is None
    trace = _trace()
    least = sqcost.sq_job_least_bytes(8, 14)
    red = sqscopes.reduce_jobs(trace, TABLES)
    walls = [9e-6, 1e-5, 1.2e-5, 8e-6, 1.1e-5]
    ctx = {"_sq_scoped": red, "trace": devtrace.reduce_xplane(trace),
           "device": {"kind": "TPU v5 lite",
                      "memory_peak_bytes": 9_900_000_000},
           "least_bytes": least, "job_walls": walls}
    obs.enable(install_hooks=False)
    try:
        lab = dict(tier="windowed", backend="dot")
        for _ in range(3):  # the warm-up job and two more
            obs.count("spgemm.job.jobs", **lab)
            obs.count("spgemm.job.nnz_out", 14, **lab)
        value = read(ctx)
    finally:
        obs.disable()
        obs.reset()
    device_s = DEVICE * NS
    want = {
        "sq_device_ms": 1e3 * device_s,
        "sq_dot_ms": (2 * DENS + 2 * DOT + 200) * 1e-6,
        "sq_extract_ms": (2 * (BODY + 20) + DIG) * 1e-6,
        "sq_host_gap_ms": 1e3 * (1e-5 - device_s),
        "sq_mnnz_out_per_s": 14 / 1e-5 / 1e6,
        "sq_hbm_share": 100 * (least / 819e9) / device_s,
        "sq_hbm_peak_gb": 9.9,
    }[name]
    assert value == pytest.approx(want)
    if name == "sq_hbm_share":
        assert 0 < value < 100


# --- what the cell added ----------------------------------------------------


def test_the_cell_its_configuration_and_its_seven_readers_are_appended():
    """Order checks only: whatever a later PR appends, these hold."""
    spec = _spec()
    cells = [w["name"] for w in spec.doc["workloads"]]
    configs = [c["name"] for c in spec.doc["configs"]]
    assert cells.index(TC_CELL) < cells.index(CELL)
    assert configs.index(TC_CONFIG) < configs.index(CONFIG)
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
    assert at("g500-s20.k2-batch") < at(TC_CELL) < at(CELL)
    # the seven, in the issue's order, after TC's six, listing this cell
    # alone
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert [n for n in names if n in READERS] == READERS
    assert names.index("tc_hbm_peak_gb") < names.index(READERS[0])
    for m in joined[1:]:
        assert m["workloads"] == [CELL] and m["moves"] == "mteps"
        assert m["layer"] == "algorithms + local kernels"
    by = {m["name"]: m for m in joined}
    assert [(by[n]["unit"], by[n]["better"], by[n]["source"])
            for n in READERS] == [
        ("ms", "lower", "device_trace"), ("ms", "lower", "device_trace"),
        ("ms", "lower", "device_trace"), ("ms", "lower", "host_clock"),
        ("Mnnz/s", "higher", "program_counter"),
        ("%", "higher", "device_trace"), ("GB", "lower", "program_counter")]
    mine = {m["name"] for m in spec.metrics_for(CELL, "per_layer")}
    assert set(READERS) | {"compiles_in_window", "load_s",
                           "warmup_s"} <= mine
    assert not any(m.startswith(("bfs_", "k2_", "cc_", "tc_"))
                   for m in mine)
    # and no other cell reports them
    for other in cells:
        if other != CELL:
            assert not set(READERS) & {m["name"] for m in spec.metrics_for(
                other, "per_layer")}
    # every file of the cell is new beside the ones that were there
    for rel in ("sqref.py", "sqcontrol.py", "sqcost.py", "sqscopes.py",
                "drivers/library_product.py", "traffic/spgemm-batch.json",
                "configs/g500-sq-1x1.json"):
        assert os.path.isfile(os.path.join(CHECKOUT, "chipbench", rel))


def test_the_configuration_states_its_cut_and_its_guarantees():
    spec = _spec()
    cfg = spec.config(CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "SCALE22RMATRMAT" in cfg["source"]
    assert entry["reduced"] == ["scale"] == list(cfg["reduced"])
    assert cfg["reduced"]["scale"].startswith(f"22 (upstream's pair) -> "
                                              f"{cfg['scale']}")
    assert "MEMORY" in cfg["reduced"]["scale"]
    assert (cfg["grid"], cfg["edgefactor"], cfg["graph_seed"]) == (
        [1, 1], 16, 1)
    assert cfg["scale"] in (14, 15, 16)
    assert cfg["kinds"] == [] and cfg["keep_coo"] is False
    # the generator's shape is every one-chip configuration's
    base = spec.config("g500-s20-1x1")
    assert all(cfg[k] == base[k] for k in (
        "grid", "edgefactor", "graph_seed", "rmat"))
    assert {"operands", "unit_values", "precision", "job", "tier",
            "graph_seed", "upload"} <= set(cfg["assumed"])
    assert "EQUALITY" in cfg["assumed"]["unit_values"]
    assert "draws NOTHING a job reads" in cfg["assumed"]["graph_seed"]
    assert {"digest", "entries", "window"} == set(cfg["guarantees"])
    assert "equality" in cfg["guarantees"]["digest"]
    assert "every entry" in cfg["guarantees"]["entries"]
    mix = spec.traffic(MIX)
    assert mix["entry"] == "combblas_tpu.parallel.spgemm:spgemm_job"
    assert mix["semiring"] == "combblas_tpu.semiring:PLUS_TIMES"
    assert mix["check"] == {"sampled": 4}
    assert mix["trace"] == {"start_s": 10.0, "seconds": 20.0}
    # the mix passes on what the library's own rule picks for the chip at
    # the configuration's size, and the precision the file names
    from combblas_tpu.parallel import spgemm as S
    from combblas_tpu.semiring import PLUS_TIMES

    n = 1 << cfg["scale"]
    products = {14: 1.566e8, 15: 4.432e8, 16: 1.244e9}[cfg["scale"]]
    assert mix["job"]["backend"] == S.JOB_BACKEND
    assert mix["job"]["tier"] == S.choose_tier_from_counts(
        PLUS_TIMES, n, n * n, 1, products, S.JOB_BACKEND, k_dim=n, n_dim=n)
    assert mix["job"]["mode"] in cfg["assumed"]["precision"]


# --- the cell, rehearsed ----------------------------------------------------


def test_the_cell_through_the_real_command(tmp_path):
    bench = small_benchmark(str(tmp_path), scale=8)
    # (a seed beyond 32 signed bits, as the driver's are)
    r, line = run_cell(bench, CELL, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"mteps", "setup_s"} and m["mteps"] > 0
    n, rows, cols, _ = graph.rmat_graph(8, 16, 1)
    dense = _dense_square(n, rows, cols)
    assert "warm-up job: " in r.stderr
    # the rehearsal runs the tier and backend the chip runs
    assert "tier windowed under dot" in r.stderr
    assert (f"sq: the reference's C has {np.count_nonzero(dense)} entries "
            f"of sum {dense.sum()} (the largest {dense.max()}) from "
            f"{dense.sum()} products of {len(rows)} nonzeros, "
            f"{len(rows) // 2} undirected edges of {n} vertices") in r.stderr
    assert (f"and the last job's {np.count_nonzero(dense)} stored entries "
            "against it (limit: equality)") in r.stderr
    assert (f"sq: the first job {np.count_nonzero(dense)} entries of sum "
            f"{dense.sum()}") in r.stderr
    r, line = run_cell(bench, CELL, trace=1, seed=4, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"deployment {CONFIG}: snapshot" in r.stderr
    m = check_line(line)
    assert {"load_s", "warmup_s", "compiles_in_window",
            "sq_mnnz_out_per_s"} <= set(m)
    assert m["compiles_in_window"] == 0
    assert m["sq_mnnz_out_per_s"] > 0
    # the program's own span, with the labels of what it ran
    assert "boot span spgemm.job" in r.stderr
    # the device trace's readers find no device plane on a CPU: left
    # out of the line, never 0
    assert not (set(READERS) - {"sq_mnnz_out_per_s"}) & set(m)
