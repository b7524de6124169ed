"""The ELL sweep's work as the benchmark reads it (PR 50), without the
chip: ``chipbench/ellwork.py`` on a registry, stage records and the small
traces of the four kinds made by hand, the seven readers on a program that
records none of it (the parent of the PR: None, never an error), what the
PR appended to ``BENCHMARK.json``, and the served cells rehearsed through
the real command at scale 9."""

import os

import pytest

from chipbench import bcscopes, ccscopes, ellwork, k3scopes, scopes
from chipbench.spec import CHECKOUT, Spec

import test_chipbench_bc as BC
import test_chipbench_cc as CC
import tiny_scoped_trace as T
from rehearse import check_line, run_cell, small_benchmark
from test_chipbench_k3 import K3_TABLE, SCOPED

NS = 1e-9
SAT = ["g500-s20.bfs-sat", "g500-s20k3.sssp-sat", "g500-s20bc.bc-sat"]
OPEN, CC_CELL = "g500-s20.bfs-open", "g500-s20cc.cc-batch"
ENTRIES = {
    "ell_mslots_per_batch": ("Mslots", "lower", "program_counter", "qps", SAT),
    "ell_skipped_share": ("%", "higher", "program_counter", "qps", SAT),
    "ell_ns_per_index": ("ns", "lower", "device_trace", "qps", SAT),
    "open_mslots_per_query": (
        "Mslots", "lower", "program_counter", "p50_ms", [OPEN]),
    "open_wave_ns_per_slot": ("ns", "lower", "host_clock", "p50_ms", [OPEN]),
    "cc_mslots_per_job": (
        "Mslots", "lower", "program_counter", "mteps", [CC_CELL]),
    "cc_ns_per_index": ("ns", "lower", "device_trace", "mteps", [CC_CELL]),
}


def _spec():
    return Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))


@pytest.fixture
def registry():
    """Telemetry on over an empty registry, wiped after."""
    from combblas_tpu import obs

    obs.reset()
    obs.enable(install_hooks=False)
    yield obs
    obs.disable()
    obs.reset()


def _count_batch(obs, kind, width, by_class, batches=1, **labels):
    """What ``ellmat.count_sweep_work`` and its caller add for one batch:
    ``by_class`` = ``[(slots gathered, slots skipped, dense, skipped)]``."""
    for cls, (gathered, left, dense, skipped) in enumerate(by_class):
        by = dict(labels, kind=kind, width=width, cls=cls)
        obs.count("ell.slots", gathered, mode="dense", **by)
        obs.count("ell.slots", left, mode="skipped", **by)
        obs.count("ell.class_sweeps", dense, mode="dense", **by)
        obs.count("ell.class_sweeps", skipped, mode="skipped", **by)
    obs.count("ell.batches", batches, kind=kind, width=width)


def _record(execute_s, width, slots, skipped, device_s=None, **labels):
    """One request's stage record, as ``obs.trace`` commits it."""
    execute = {"stage": "execute", "s": execute_s}
    if device_s is not None:
        execute["parts"] = [
            {"stage": "launch", "s": 0.001},
            {"stage": "device", "s": device_s},
            {"stage": "readback", "s": execute_s - device_s - 0.001}]
    return {"t0": 1.0, "wall_s": execute_s + 0.01,
            "stages": [{"stage": "queue_wait", "s": 0.004}, execute,
                       {"stage": "scatter", "s": 0.006}],
            "labels": dict({"status": "ok", "width": width, "slots": slots,
                            "slots_skipped": skipped}, **labels)}


# --- the counters -----------------------------------------------------------


def test_the_counters_make_the_by_class_table(registry, capfd):
    ctx = {"mix": {"kind": "sssp"}, "device": {"count": 1}}
    ellwork.log_by_class(dict(ctx))  # no series: no table
    assert "by degree class" not in capfd.readouterr().err
    # two batches of two classes (3,000 and 1,000,000 slots), four rounds
    # and five: the wide class skipped once and twice
    _count_batch(registry, "sssp", 16, [
        (12_000, 0, 4, 0), (3_000_000, 1_000_000, 3, 1)])
    _count_batch(registry, "sssp", 16, [
        (15_000, 0, 5, 0), (3_000_000, 2_000_000, 3, 2)])
    # another kind's and another family's series are not this cell's
    _count_batch(registry, "cc", 1, [(7, 0, 1, 0)])
    registry.count("serve.sssp.rounds", 9, width=16)
    assert ellwork.total("ell.slots", kind="sssp") == 9_027_000
    assert ellwork.total("ell.slots", kind="bfs") is None
    # a served cell's numbers are its stage records', not the counters'
    assert ellwork.mslots_per_batch(ctx) is None
    assert ellwork.skipped_share(ctx) is None
    read = _spec().load_module("layers", "ell_mslots_per_batch").read
    assert read(ctx) is None
    err = capfd.readouterr().err
    assert "ell work of kind sssp by degree class, 2 batches" in err
    assert "class 0: slots 3000, dense 9, skipped 0, 0.45%" in err
    assert "class 1: slots 1000000, dense 6, skipped 3, 99.55%" in err
    read(ctx)  # the table is logged once a run
    assert "by degree class" not in capfd.readouterr().err


def test_phases_of_bc_add_up_and_a_mesh_s_table_divides_by_its_tiles(
        registry, capfd):
    ctx = {"mix": {"kind": "bc"}, "device": {"count": 4}}
    # four tiles: sweeps are counted over all of them, slots are the
    # busiest one's
    _count_batch(registry, "bc", 16, [(5_000, 1_000, 20, 4)], phase="forward")
    _count_batch(registry, "bc", 16, [(2_000, 3_000, 8, 12)], batches=0,
                 phase="backward")
    ellwork.log_by_class(ctx)
    assert "class 0: slots 1000, dense 28, skipped 16, 100.00%" in (
        capfd.readouterr().err)


def test_the_cc_cell_s_slots_a_job(registry):
    ctx = {"mix": {"driver": "library_job"}, "device": {"count": 1}}
    read = _spec().load_module("layers", "cc_mslots_per_job").read
    assert read(dict(ctx)) is None
    for _ in range(3):  # the warm-up job and two more: four sweeps each
        _count_batch(registry, "cc", 1, [
            (4 * 953_104, 0, 4, 0), (4 * 36_000_000, 0, 4, 0)])
    assert ellwork.mslots_per_batch(ctx, "cc") == pytest.approx(
        4 * 36.953104)
    assert read(ctx) == pytest.approx(147.812416)
    assert ellwork.mslots_per_batch(ctx) is None  # no served kind


# --- the stage records ------------------------------------------------------


def test_batches_are_grouped_by_their_execute_and_carry_their_work(capfd):
    stages = (
        [_record(0.250, 16, 90_000_000, 10_000_000, 0.200)] * 12
        + [_record(0.130, 4, 60_000_000, 40_000_000, 0.120)] * 3
        + [_record(0.131, 4, 70_000_000, 30_000_000, 0.126)] * 2
        + [_record(0.100, 1, 50_000_000, 50_000_000, 0.090)]
        # a failed request, and a record of a program without the label
        + [_record(0.100, 1, 1, 1, 0.09, status="error")]
        + [{"t0": 1.0, "wall_s": 0.2, "labels": {"status": "ok", "width": 1},
            "stages": [{"stage": "execute", "s": 0.19}]}])
    ctx = {"stages": stages}
    got = sorted(ellwork.batches(ctx), key=lambda b: -b["slots"])
    assert [(b["width"], b["requests"], b["slots"], b["device_s"])
            for b in got] == [
        (16, 12, 90_000_000, 0.200), (4, 2, 70_000_000, 0.126),
        (4, 3, 60_000_000, 0.120), (1, 1, 50_000_000, 0.090)]
    assert ellwork.mslots_per_query(ctx) == pytest.approx(270 / 18)
    # ns a slot by batch: 2.222 1.8 2.0 1.8
    assert ellwork.wave_ns_per_slot(ctx) == pytest.approx(1.9)
    err = capfd.readouterr().err
    assert ("waves of width 1: 1, median wave 90.0 ms, median 50.00 "
            "Mslots, median 1.800 ns a slot") in err
    assert ("waves of width 4: 2, median wave 123.0 ms, median 65.00 "
            "Mslots, median 1.900 ns a slot") in err
    assert "waves of width 16: 1, median wave 200.0 ms" in err
    ellwork.wave_ns_per_slot(ctx)
    assert "waves of width" not in capfd.readouterr().err
    # records without parts (an untraced batch): work, and no wave
    bare = {"stages": [_record(0.25, 16, 9_000_000, 0)] * 4}
    assert ellwork.mslots_per_query(bare) == pytest.approx(9 / 4)
    assert ellwork.wave_ns_per_slot(bare) is None


def test_slots_a_batch_and_the_skipped_share_are_the_dominant_width_s():
    """One source a number: the records of the width that gathered most
    (a closed loop's only width; the other records are a drain's)."""
    stages = ([_record(0.25, 16, 3_012_000, 1_000_000)] * 16
              + [_record(0.26, 16, 3_015_000, 2_000_000)] * 16
              + [_record(0.10, 4, 5_000_000, 0)] * 3)
    ctx = {"mix": {"kind": "sssp"}, "stages": stages}
    assert [b["width"] for b in ellwork.dominant(ctx)] == [16, 16]
    assert ellwork.mslots_per_batch(ctx) == pytest.approx(6.027 / 2)
    assert ellwork.skipped_share(ctx) == pytest.approx(100 * 3 / 9.027)
    assert _spec().load_module("layers", "ell_skipped_share").read(
        ctx) == pytest.approx(100 * 3 / 9.027)
    assert ellwork.dominant({}) == []


# --- the device trace -------------------------------------------------------


def _want(red, loops):
    """gather + fold seconds an execution under ``loops``, by hand from
    the reduction's own table."""
    return sum(s for lab, s in red["by_scope"].items()
               if lab.split("/")[0] in loops
               and lab.endswith(("/gather", "/fold")))


@pytest.mark.parametrize("kind", ["bfs", "sssp", "bc"])
def test_ns_an_index_of_a_served_kind_on_its_small_trace(kind, capfd):
    red, loops = {
        "bfs": lambda: (scopes.reduce_scopes(SCOPED, {T.MODULE: T.TABLE}),
                        ("bfs.level",)),
        "sssp": lambda: (k3scopes.reduce_scopes(SCOPED, {T.MODULE: K3_TABLE}),
                         ("sssp.round",)),
        "bc": lambda: (bcscopes.reduce_scopes(
            BC._trace(), {BC.MODULE: BC.TABLE}),
            ("bc.forward", "bc.backward")),
    }[kind]()
    seconds = ellwork.sweep_seconds(red, kind)
    assert seconds == pytest.approx(_want(red, loops)) and seconds > 0
    if kind == "sssp":  # the parents pass sweeps outside the tally
        assert red["by_scope"]["sssp.parents/ell.bucket0/gather"] > 0
        assert seconds == pytest.approx(
            sum(red["by_scope"][f"sssp.round/ell.bucket0/{leaf}"]
                for leaf in ("gather", "fold")))
    if kind == "bc":
        assert seconds == pytest.approx(
            (sum(map(sum, BC.FORWARD + BC.BACKWARD)) + 12 * BC.FOLD) / 2 * NS)
    # the program's width is its module's; other widths' batches are not
    # this execution's
    stages = ([_record(0.25, 16, 400, 100)] * 16
              + [_record(0.26, 16, 600, 100)] * 16
              + [_record(0.10, 4, 77, 0)] * 4)

    def ctx(stages, **found):
        return dict({"mix": {"kind": kind}, "stages": stages}, **found)

    read = _spec().load_module("layers", "ell_ns_per_index").read
    assert read(ctx(stages, _scoped=red)) == pytest.approx(
        1e9 * seconds / 500)
    assert "2 batches of 0.001 Mslots (mean)" in capfd.readouterr().err
    # slots x ns is the scopes' milliseconds, by one account
    assert ellwork.mslots_per_batch(ctx(stages)) * read(
        ctx(stages, _scoped=red)) == pytest.approx(1e3 * seconds)
    # on a mesh the seconds are a mean over the planes and the slots the
    # busiest tile's: no reading
    assert read(ctx(stages, _scoped=red, device={"count": 4})) is None
    # no batch of that width, no scope, no trace: nothing
    assert read(ctx(stages[-4:], _scoped=red)) is None
    bare = scopes.reduce_scopes(SCOPED, {})
    assert read(ctx(stages, _scoped=bare)) is None
    assert read(ctx(stages)) is None


def test_ns_an_index_of_a_fastsv_job_on_its_small_trace(registry):
    red = ccscopes.reduce_scopes(CC._trace(), {CC.MODULE: CC.TABLE})
    seconds = ellwork.sweep_seconds(red, "cc")
    # the sweep's gather and fold, not the round's own ``cc.gather``
    rounds = sum(map(len, CC.ROUNDS))
    assert seconds == pytest.approx(
        (sum(map(sum, CC.ROUNDS)) + rounds * CC.FOLD) / 2 * NS)
    read = _spec().load_module("layers", "cc_ns_per_index").read
    ctx = {"_scoped": red}
    assert read(ctx) is None  # no counter
    for _ in range(2):
        _count_batch(registry, "cc", 1, [(3 * 32, 0, 3, 0)])
    assert read(ctx) == pytest.approx(1e9 * seconds / 96)


# --- a program that records none of it -------------------------------------


@pytest.mark.parametrize("name", list(ENTRIES))
def test_a_reader_finds_nothing_on_the_parent_s_program(name):
    """Counters, stage records and a scoped trace of a program from
    before the family (no ``ell.*`` series, no ``slots`` label): every
    reader returns None and none raises."""
    from combblas_tpu import obs

    read = _spec().load_module("layers", name).read
    obs.reset()
    assert read({}) is None
    old = {"t0": 1.0, "wall_s": 0.3, "labels": {
               "status": "ok", "width": 16, "plan": "warm", "version": 1},
           "stages": [{"stage": "execute", "s": 0.25, "parts": [
               {"stage": "device", "s": 0.2}]}]}
    for kind, red in (
            ("bfs", scopes.reduce_scopes(SCOPED, {T.MODULE: T.TABLE})),
            (None, ccscopes.reduce_scopes(CC._trace(), {CC.MODULE: CC.TABLE}))):
        obs.enable(install_hooks=False)
        try:
            obs.count("models.cc.jobs", 3)
            obs.count("serve.bfs.sweeps", 40, mode="dense")
            ctx = {"_scoped": red, "stages": [old] * 16,
                   "mix": {"kind": kind} if kind else {"driver": "library_job"},
                   "device": {"count": 1}}
            assert read(ctx) is None
        finally:
            obs.disable()
            obs.reset()


# --- BENCHMARK.json ---------------------------------------------------------


def test_the_seven_entries_are_appended_with_their_cells():
    spec = _spec()
    entries = {m["name"]: m for m in spec.doc["per_layer"]}
    names = list(entries)
    assert [n for n in names if n in ENTRIES] == list(ENTRIES)
    assert names.index("sqm_hbm_peak_gb") < names.index(next(iter(ENTRIES)))
    for name, (unit, better, source, moves, cells) in ENTRIES.items():
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["moves"],
                m["workloads"]) == (unit, better, source, moves, cells)
        assert m["layer"] == "algorithms + local kernels"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # five of the seven cells that run the class loop: the W = 256 batch
    # program has no tally, and the mesh cell waits for a chip run
    touched = {c for m in ENTRIES.values() for c in m[4]}
    assert len(touched) == 5 and "g500-s20.k2-batch" not in touched
    for cell in touched:
        mine = {m["name"] for m in spec.metrics_for(cell, "per_layer")}
        assert mine & set(ENTRIES) == {
            n for n, e in ENTRIES.items() if cell in e[4]}


# --- through the real command ----------------------------------------------


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return small_benchmark(str(tmp_path_factory.mktemp("ellwork")))


@pytest.mark.parametrize("cell", SAT)
def test_a_served_closed_cell_reports_its_sweeps_work(bench, cell):
    r, line = run_cell(bench, cell, trace=1, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    # counters on any platform; the trace's reader finds no device plane
    assert m["ell_mslots_per_batch"] > 0 and "ell_ns_per_index" not in m
    assert 0 < m["ell_skipped_share"] < 100
    assert f"ell work of kind {cell.split('.')[1][:-4]}" in r.stderr
    by_class = [ln for ln in r.stderr.splitlines() if "] class " in ln]
    assert len(by_class) >= 10 and all(
        "dense" in ln and "skipped" in ln for ln in by_class)
    # with telemetry off the line holds the end-to-end metrics alone
    r, line = run_cell(bench, cell, trace=0, seed=5, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert set(check_line(line)) == {"qps", "setup_s"}
    assert "ell work" not in r.stderr


def test_the_open_cell_reports_slots_a_query_and_logs_waves_by_width(bench):
    r, line = run_cell(bench, OPEN, trace=1, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert m["open_mslots_per_query"] > 0 and m["open_wave_ns_per_slot"] > 0
    assert "ell_mslots_per_batch" not in m
    widths = [ln.split("waves of width ")[1].split(":")[0]
              for ln in r.stderr.splitlines() if "waves of width" in ln]
    assert widths and set(widths) <= {"1", "4", "16"}
    assert "ell work of kind bfs by degree class" in r.stderr
