"""The mesh product cell's part of the benchmark without the chip: the
driver's read of every tile and its block-by-block check, each tripped
by one planted wrong entry; the mesh control's bfloat16 accumulator; the
mesh cost function by hand; the ten readers on a small trace of FOUR
device planes (and without the job's annotation or its tables); what the
cell added to ``BENCHMARK.json`` (order checks, no place pinned); and
one rehearsal of ``g500-sq15x4.spgemm-mesh`` through the real command on
four host devices at scale 9, untraced and traced."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import (devtrace, graph, sqmcontrol, sqmcost, sqmscopes,
                       sqref, sqscopes)
from chipbench.spec import CHECKOUT, Spec

from rehearse import check_line, run_cell, small_benchmark

NS = 1e-9
CELL, CONFIG, MIX = "g500-sq15x4.spgemm-mesh", "g500-sq15-2x2", "spgemm-mesh"
ONE_CELL, ONE_CONFIG = "g500-sq.spgemm-batch", "g500-sq-1x1"
READERS = ["sqm_device_ms", "sqm_dot_ms", "sqm_extract_ms",
           "sqm_exchange_ms", "sqm_host_gap_ms", "sqm_collective_share",
           "sqm_device_skew", "sqm_mnnz_out_per_s", "sqm_hbm_share",
           "sqm_hbm_peak_gb"]
GRID = (2, 2)


def _spec():
    return Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def s7():
    n, rows, cols, _ = graph.rmat_graph(7, 16, 1)
    return n, rows, cols, sqref.SQReference(n, rows, cols)


# --- the driver ------------------------------------------------------------


def test_driver_reads_every_tile_in_global_coordinates(s7):
    """``stored_tiles`` of a C left on a 2 x 2 mesh by the job itself:
    four tiles, each its block of the reference, none sharing a
    coordinate, padding left behind."""
    from combblas_tpu.parallel import spgemm as S
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.spmat import SpParMat
    from combblas_tpu.semiring import PLUS_TIMES

    n, rows, cols, ref = s7
    drv = _spec().load_module("drivers", "library_product_mesh")
    A = SpParMat.from_global_coo(
        Grid.make(*GRID), rows, cols, np.ones(len(rows), np.float32), n, n)
    C, digest = S.spgemm_job(
        PLUS_TIMES, A, A, tier="windowed", backend="dot", mode="bf16",
        block_rows=16, block_cols=32)
    tiles = drv.stored_tiles(C)
    assert [t[:2] for t in tiles] == [(0, 0), (0, 1), (1, 0), (1, 1)]
    lr = n // 2
    for i, j, r, c, v in tiles:
        assert len(r) == len(c) == len(v) == int(C.nnz[i, j])
        assert (r // lr == i).all() and (c // lr == j).all()
    assert sum(len(t[2]) for t in tiles) == ref.C.nnz == digest["nnz"]
    assert max(len(t[2]) for t in tiles) == C.capacity
    assert drv.check_tiles(ref, tiles, GRID) == []
    # the tiles together are the whole matrix, entry for entry
    whole = [np.concatenate([t[k] for t in tiles]) for k in (2, 3, 4)]
    assert ref.check_entries(*whole) is None
    a_rows, a_cols, c_tiles = drv.block_counts(ref, rows, cols, GRID)
    assert a_rows == [int((rows // lr == i).sum()) for i in range(2)]
    assert a_cols == [int((cols // lr == j).sum()) for j in range(2)]
    assert c_tiles == [[len(tiles[0][2]), len(tiles[1][2])],
                       [len(tiles[2][2]), len(tiles[3][2])]]


def test_driver_holds_every_tile_to_the_reference_s_own_block(s7):
    n, rows, cols, ref = s7
    drv = _spec().load_module("drivers", "library_product_mesh")
    tiles = [(i, j, r, c, v.astype(np.float32))
             for i, j, r, c, v in sqmcontrol.tiles_of(ref.C, GRID)]
    assert drv.check_tiles(ref, tiles, GRID) == []
    i, j, r, c, v = tiles[2]

    def with_tile(k, new):
        return tiles[:k] + [new] + tiles[k + 1:]

    # ONE wrong entry planted in one tile: that tile alone is refused
    planted = v.copy()
    planted[len(v) // 2] += 1
    found = drv.check_tiles(ref, with_tile(2, (i, j, r, c, planted)), GRID)
    assert len(found) == 1 and found[0].startswith(
        "the last job's C, tile (1, 0): 1 of ")
    assert "hold another value" in found[0]
    # one dropped; one stored twice
    found = drv.check_tiles(
        ref, with_tile(2, (i, j, r[1:], c[1:], v[1:])), GRID)
    assert len(found) == 1 and "1 coordinates are in one and not" in found[0]
    found = drv.check_tiles(ref, with_tile(2, (
        i, j, np.append(r, r[0]), np.append(c, c[0]), np.append(v, 0))),
        GRID)
    assert found == ["the last job's C, tile (1, 0): 1 stored tuples "
                     "repeat a coordinate"]
    # an entry of another tile's block: refused by its coordinates, and
    # missed where it belongs
    oi, oj, orr, oc, ov = tiles[1]
    found = drv.check_tiles(ref, [
        tiles[0], (oi, oj, orr[1:], oc[1:], ov[1:]),
        (i, j, np.append(r, orr[0]), np.append(c, oc[0]),
         np.append(v, ov[0])), tiles[3]], GRID)
    assert len(found) == 2
    assert found[0].startswith("the last job's C, tile (0, 1): ")
    assert found[1] == ("the last job's C, tile (1, 0): a stored tuple "
                        "lies outside the tile's block")
    # a tile missing: no comparison is made on three
    assert drv.check_tiles(ref, tiles[:3], GRID)[0].startswith(
        "the last job's C: tiles ")


def test_driver_ends_the_run_at_once_on_a_program_without_the_entry():
    drv = _spec().load_module("drivers", "library_product_mesh")

    class Job:
        mix = {"entry": "combblas_tpu.parallel.spgemm:no_such_entry",
               "semiring": "combblas_tpu.semiring:PLUS_TIMES"}

        def deploy(self):
            raise AssertionError("the graph was loaded first")

    with pytest.raises(SystemExit) as e:
        drv.run(Job())
    assert "no 'combblas_tpu.parallel.spgemm:no_such_entry'" in str(e.value)
    assert e.value.code != 0


def test_a_benchmark_without_the_cell_ends_at_once(tmp_path):
    """The parent of this PR given the cell's name: no such workload,
    non-zero, within seconds, before JAX starts."""
    def drop(doc):
        doc["workloads"] = [w for w in doc["workloads"]
                            if w["name"] != CELL]

    bench = small_benchmark(str(tmp_path), scale=8, extra=drop)
    r, line = run_cell(bench, CELL, seconds=1, devices=4)
    assert r.returncode != 0 and line is None
    assert f"no workload named '{CELL}'" in r.stderr


def test_the_mesh_control_refuses_a_bfloat16_accumulator(tmp_path):
    bench = small_benchmark(str(tmp_path), scale=10)
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.sqmcontrol", "--bench", bench,
         "--seed", "2300001111"],
        cwd=CHECKOUT, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    out = {o["held_in"]: o for o in map(
        json.loads, r.stdout.strip().splitlines())}
    assert list(out) == ["float32", "bfloat16", "bfloat16_stalled"]
    exact = out["float32"]
    assert exact["correct"] is True and exact["differing_entries"] == 0
    assert exact["tiles_refused"] == 0 and exact["largest"] > 256
    n, rows, cols, _ = graph.rmat_graph(10, 16, 1)
    data = sqref.SQReference(n, rows, cols).C.data
    from chipbench import sqcontrol

    once, stalled = out["bfloat16"], out["bfloat16_stalled"]
    assert once["correct"] is False and stalled["correct"] is False
    assert once["differing_entries"] == int(
        (sqcontrol.round_to_bfloat16(data) != data).sum()) > 0
    assert stalled["differing_entries"] == int((data > 256).sum())
    assert 1 <= once["tiles_refused"] <= stalled["tiles_refused"] <= 4
    assert any("tile (" in p and "hold another value" in p
               for p in once["problems"])


def test_the_control_on_the_devices_runs_one_real_job_through_the_checks(
        tmp_path):
    """``--on-mesh``: the job itself, on four host devices, with every
    stage product's result held in the given precision: float32 is the
    cell's own program and passes; bfloat16 is refused by tiles that
    hold other values at the reference's coordinates."""
    bench = small_benchmark(str(tmp_path), scale=10)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.sqmcontrol", "--bench", bench,
         "--seed", "2300001111", "--on-mesh"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    out = {o["held_in"]: o for o in map(
        json.loads, r.stdout.strip().splitlines())}
    assert list(out) == ["float32", "bfloat16"]
    exact, once = out["float32"], out["bfloat16"]
    assert exact["on_mesh"] and exact["correct"] is True
    assert exact["differing_entries"] == 0 == exact["tiles_refused"]
    assert sum(exact["stored"]) == exact["entries"] == sum(once["stored"])
    assert once["correct"] is False and once["differing_entries"] > 0
    assert 1 <= once["tiles_refused"] <= 4
    assert any("tile (" in p and "hold another value" in p
               for p in once["problems"])


# --- the cost ---------------------------------------------------------------


def test_least_bytes_of_a_mesh_job_by_hand():
    # scale 15 on 2 x 2 (the reference's counts): chip (0, 0) reads A's
    # first row block and first column block and writes the fullest tile
    assert sqmcost.sq_mesh_job_least_bytes(
        [454_514, 428_980], [454_514, 428_980],
        [[30_650_448, 30_208_394], [30_208_394, 29_816_101]]) == 12 * (
        454_514 + 454_514 + 30_650_448) == 378_713_712
    # the fullest chip need not be the one with the fullest tile
    assert sqmcost.sq_mesh_job_least_bytes(
        [1, 50], [2, 3], [[10, 5], [4, 6]]) == 12 * (50 + 3 + 6)
    assert sqmcost.sq_mesh_job_least_bytes([8], [8], [[14]]) == 360


# --- the readers -----------------------------------------------------------

FLOPS, FUSED, COUNTS, PACK, DIGEST = (
    "jit_summa_stage_flops", "jit_summa_spgemm_windowed",
    "jit__tile_chunk_counts", "jit__pack_tiles", "jit_spgemm_digest")
_P = "jit(f)/jit(main)/shard_map/"
TABLES = {
    FLOPS: {"fusion.1": _P + "sq.symbolic/reduce_sum",
            "all-gather.7": _P + "all_gather"},
    FUSED: {"all-gather.7": _P + "sq.exchange/all_gather",
            "fusion.1": _P + "sq.densify/scatter-add",
            "fusion.2": _P + "sq.dot/dot_general",
            "while.3": _P + "sq.extract/while"},
    COUNTS: {"fusion.1": _P + "sq.pack/reduce_sum"},
    PACK: {"fusion.1": _P + "sq.pack/dynamic_update_slice"},
    DIGEST: {"fusion.1": _P + "sq.digest/sort",
             "all-reduce.8": _P + "sq.digest/psum"},
}
SYM, GATHER, XCH, DENS, DOT, BODY, CNT, PCK, DIG, RED = (
    100, 30, 60, 300, 2000, 1500, 40, 80, 200, 20)
GAP = 500  # the host between two programs
#: what each device's stage products take beyond ``DOT``: the planes' skew
SLOW = (0, 100, 200, 400)
_OPS = ["fusion.1", "fusion.2", "while.3", "fusion.4", "all-gather.7",
        "all-reduce.8", "copy.9"]
_KIND = {"all-gather.7": "all-gather", "all-reduce.8": "all-reduce"}
_MODS = [FLOPS + "(1)", FUSED + "(2)", COUNTS + "(3)", PACK + "(4)",
         DIGEST + "(5)"]
_ID = {name: i + 1 for i, name in enumerate(_OPS + _MODS + ["spgemm.job"])}
JOB_NS = 5 * GAP + SYM + GATHER + XCH + DENS + DOT + max(SLOW) + (
    BODY + 20) + CNT + PCK + DIG + RED + GAP


def _ev(name, start, end):
    return (f"events {{ metadata_id: {_ID[name]} offset_ps: {start * 1000} "
            f"duration_ps: {(end - start) * 1000} }}")


def _job(t0, slow):
    """One job's device events on one plane from ``t0``: every program
    starts where the host launched it (the same instant on every
    plane); a faster plane idles to the next launch."""
    ops, mods = [], []
    starts = iter(np.cumsum([GAP, SYM + GATHER + GAP,
                             XCH + DENS + DOT + max(SLOW) + BODY + 20 + GAP,
                             CNT + GAP, PCK + GAP]) + t0)

    def program(mod, steps):
        t = start = int(next(starts))
        for name, ns in steps:
            if name == "while.3":  # the loop holds one unnamed body op
                ops.append(_ev("while.3", t, t + ns))
                ops.append(_ev("fusion.4", t + 10, t + ns - 10))
            else:
                ops.append(_ev(name, t, t + ns))
            t += ns
        mods.append(_ev(mod, start, t))

    program(_MODS[0], [("all-gather.7", GATHER), ("fusion.1", SYM)])
    program(_MODS[1], [("all-gather.7", XCH), ("fusion.1", DENS),
                       ("fusion.2", DOT + slow), ("while.3", BODY + 20)])
    program(_MODS[2], [("fusion.1", CNT)])
    program(_MODS[3], [("fusion.1", PCK)])
    program(_MODS[4], [("fusion.1", DIG), ("all-reduce.8", RED)])
    return ops, mods


def _trace(annotated=True, planes=4) -> bytes:
    from jax.profiler import ProfileData

    per = [([_ev("copy.9", 100, 200)], []) for _ in range(planes)]
    host, t = [], 1000
    for _ in range(2):
        for k in range(planes):
            o, m = _job(t, SLOW[k])
            per[k][0].extend(o)
            per[k][1].extend(m)
        host.append(_ev("spgemm.job", t, t + JOB_NS))
        t += JOB_NS + 100
    # a third job, cut by the trace's end on every plane
    for k in range(planes):
        o, m = _job(t, SLOW[k])
        per[k][0].extend(o[:3])
        per[k][1].extend(m[:1])
    host.append(_ev("spgemm.job", t, t + JOB_NS))

    def text(n):
        if n not in _OPS:
            return f'"{n}"'
        return f'"%{n} = f32[64]{{0}} {_KIND.get(n, "fusion")}(%p)"'

    meta = " ".join(
        f"event_metadata {{ key: {i} value {{ id: {i} name: {text(n)} }} }}"
        for n, i in _ID.items())

    def plane(pid, name, lines):
        body = " ".join(
            f'lines {{ id: {k + 1} name: "{nm}" timestamp_ns: 0 '
            + " ".join(evs) + " }" for k, (nm, evs) in enumerate(lines))
        return f'planes {{ id: {pid} name: "{name}" {body} {meta} }}'

    return ProfileData.text_proto_to_serialized_xspace(
        " ".join(plane(k + 1, f"/device:TPU:{k}", (
            ("XLA Modules", per[k][1]), ("XLA Ops", per[k][0])))
            for k in range(planes))
        + " " + plane(9, "/host:CPU", (
            ("python3", host if annotated else []),)))


#: a plane's busy nanoseconds inside one job, by hand
def _busy(k):
    return (SYM + GATHER + XCH + DENS + DOT + SLOW[k] + BODY + 20 + CNT
            + PCK + DIG + RED)


COLL = GATHER + XCH + RED


@pytest.fixture
def no_slack(monkeypatch):
    """The hand-made trace's jobs are microseconds long: the millisecond
    a job may reach back before its annotation would take in the stray
    operation that opens the trace."""
    monkeypatch.setattr(sqscopes, "ALIGN_S", 0.0)


def test_a_mesh_job_reduced_on_every_plane_and_read_on_the_busiest(no_slack):
    red = sqmscopes.reduce_jobs(_trace(), TABLES)
    assert red["jobs"] == 2 and red["wall_s"] == pytest.approx(JOB_NS * NS)
    assert list(red["devices"]) == [f"/device:TPU:{k}" for k in range(4)]
    assert red["busiest"] == "/device:TPU:3"
    for k, d in enumerate(red["devices"].values()):
        assert d["device_s"] == pytest.approx(_busy(k) * NS)
        assert d["collective_s"] == pytest.approx(COLL * NS)
        by = d["by_scope"]
        assert set(by) == {"sq.symbolic", "sq.exchange", "sq.densify",
                           "sq.dot", "sq.extract", "sq.pack", "sq.digest"}
        assert by["sq.exchange"] == pytest.approx(XCH * NS)
        assert by["sq.dot"] == pytest.approx((DOT + SLOW[k]) * NS)
        assert by["sq.densify"] == pytest.approx(DENS * NS)
        # the loop's unnamed body takes the loop's scope
        assert by["sq.extract"] == pytest.approx((BODY + 20) * NS)
        assert by["sq.pack"] == pytest.approx((CNT + PCK) * NS)
        assert by["sq.digest"] == pytest.approx((DIG + RED) * NS)
        # the counting pass's own gather carries no scope of the job's
        assert d["unscoped_s"] == pytest.approx(GATHER * NS)
        assert sum(by.values()) + d["unscoped_s"] == pytest.approx(
            d["device_s"])
        assert d["modules"][FUSED][0] == 1
    assert sqmscopes.label(_P + "sq.exchange/all_gather") == "sq.exchange"
    assert sqmscopes.label(_P + "sq.pack/dynamic_update_slice") == "sq.pack"
    assert sqscopes.label(_P + "sq.pack/dynamic_update_slice") is None
    ctx = {"_sqm_scoped": red}
    assert sqmscopes.scope_ms(ctx, ("sq.densify", "sq.dot")) == pytest.approx(
        (DENS + DOT + SLOW[3]) * 1e-6)
    assert sqmscopes.scope_ms(ctx, ("tc.harvest",)) is None
    # under no table a job is still timed on every plane, its
    # collectives still known by the compiler's names; no scope
    bare = sqmscopes.reduce_jobs(_trace(), {})
    top = bare["devices"][bare["busiest"]]
    assert top["by_scope"] is None
    assert top["device_s"] == pytest.approx(_busy(3) * NS)
    assert top["collective_s"] == pytest.approx(COLL * NS)
    assert sqmscopes.scope_ms({"_sqm_scoped": bare}, ("sq.dot",)) is None
    # a program that writes no annotation (the parent) has no job; one
    # plane reads like four
    assert sqmscopes.reduce_jobs(_trace(annotated=False), TABLES) is None
    one = sqmscopes.reduce_jobs(_trace(planes=1), TABLES)
    assert one["busiest"] == "/device:TPU:0" and len(one["devices"]) == 1


@pytest.mark.parametrize("name", READERS)
def test_readers_give_a_number_on_four_planes_and_none_without(
        name, no_slack, monkeypatch):
    import jax

    from combblas_tpu import obs

    read = _spec().load_module("layers", name).read
    obs.reset()
    # nothing traced, no counter, no peak, no job: None, never 0 and
    # never an exception (a CPU has no memory statistics either)
    assert read({"device": {"kind": "TPU v5 lite"}}) is None
    assert read({"_sqm_scoped": None, "job_walls": [],
                 "device": {"kind": "TPU v5 lite"}}) is None
    trace = _trace()
    least = sqmcost.sq_mesh_job_least_bytes([8, 6], [8, 6], [[14, 9], [9, 7]])
    red = sqmscopes.reduce_jobs(trace, TABLES)
    walls = [9e-6, 1e-5, 1.2e-5, 8e-6, 1.1e-5]
    ctx = {"_sqm_scoped": red, "trace": devtrace.reduce_xplane(trace),
           "device": {"kind": "TPU v5 lite",
                      "memory_peak_bytes": 4_000_000_000},
           "least_bytes": least, "job_walls": walls}

    class Chip:
        def __init__(self, in_use, reserved):
            self.stats = {"peak_bytes_in_use": in_use,
                          "peak_bytes_reserved": reserved}

        def memory_stats(self):
            return self.stats

    monkeypatch.setattr(jax, "devices", lambda: [
        Chip(4_000_000_000, 500_000_000), Chip(3_900_000_000, 900_000_000),
        Chip(3_000_000_000, 100_000_000), Chip(1, 1)])
    obs.enable(install_hooks=False)
    try:
        lab = dict(tier="windowed", backend="dot")
        for _ in range(3):  # the warm-up job and two more
            obs.count("spgemm.job.jobs", **lab)
            obs.count("spgemm.job.nnz_out", 39, **lab)
        value = read(ctx)
    finally:
        obs.disable()
        obs.reset()
    device_s = _busy(3) * NS
    want = {
        "sqm_device_ms": 1e3 * device_s,
        "sqm_dot_ms": (DENS + DOT + SLOW[3]) * 1e-6,
        "sqm_extract_ms": (BODY + 20 + CNT + PCK + DIG + RED) * 1e-6,
        "sqm_exchange_ms": XCH * 1e-6,
        "sqm_host_gap_ms": 1e3 * (1e-5 - device_s),
        "sqm_collective_share": 100 * np.mean(
            [COLL / _busy(k) for k in range(4)]),
        "sqm_device_skew": 100 * (_busy(3) - _busy(0)) / _busy(3),
        "sqm_mnnz_out_per_s": 39 / 1e-5 / 1e6,
        "sqm_hbm_share": 100 * (least / 819e9) / device_s,
        "sqm_hbm_peak_gb": 4.8,
    }[name]
    assert value == pytest.approx(want)
    if "share" in name or "skew" in name:
        assert 0 < value < 100


# --- what the cell added ----------------------------------------------------


def test_the_cell_its_configuration_and_its_ten_readers_are_appended():
    """Order checks only: whatever a later PR appends, these hold."""
    spec = _spec()
    cells = [w["name"] for w in spec.doc["workloads"]]
    configs = [c["name"] for c in spec.doc["configs"]]
    assert cells.index("hipmcl-fam.mcl-batch") < cells.index(CELL)
    assert configs.index("hipmcl-fam-1x1") < configs.index(CONFIG)
    cell = spec.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 4)
    assert len(cell["why"]) <= 200
    # the benchmark's second four-chip cell; at most half may ask for 4
    four = [w["name"] for w in spec.doc["workloads"] if w["chips"] == 4]
    assert four[:2] == ["g500-s22x4.bfs-sat", CELL]
    assert len(four) <= len(cells) // 2
    reported = {m["name"] for m in spec.metrics_for(CELL, "end_to_end")}
    assert reported == {"mteps", "setup_s"}
    joined = [m for sec in ("end_to_end", "per_layer")
              for m in spec.doc[sec] if CELL in m.get("workloads", ())]
    assert [m["name"] for m in joined] == ["mteps"] + READERS
    at = joined[0]["workloads"].index
    assert at(ONE_CELL) < at("hipmcl-fam.mcl-batch") < at(CELL)
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert [n for n in names if n in READERS] == READERS
    assert names.index("mcl_hbm_peak_gb") < names.index(READERS[0])
    layers = {m["layer"] for m in spec.doc["per_layer"]
              if m["name"] not in READERS}
    for m in joined[1:]:
        assert m["workloads"] == [CELL] and m["moves"] == "mteps"
        assert m["layer"] in layers  # spelled as the layers that were there
    by = {m["name"]: m for m in joined}
    assert {n for n in READERS if by[n]["layer"] == "distributed ops"} == {
        "sqm_exchange_ms", "sqm_collective_share", "sqm_device_skew"}
    assert [(by[n]["unit"], by[n]["better"], by[n]["source"])
            for n in READERS] == [
        ("ms", "lower", "device_trace"), ("ms", "lower", "device_trace"),
        ("ms", "lower", "device_trace"), ("ms", "lower", "device_trace"),
        ("ms", "lower", "host_clock"), ("%", "lower", "device_trace"),
        ("%", "lower", "device_trace"),
        ("Mnnz/s", "higher", "program_counter"),
        ("%", "higher", "device_trace"), ("GB", "lower", "program_counter")]
    mine = {m["name"] for m in spec.metrics_for(CELL, "per_layer")}
    assert set(READERS) | {"compiles_in_window", "load_s", "warmup_s",
                           "graph_ready_s", "upload_s"} <= mine
    assert not any(m.startswith(("bfs_", "k2_", "cc_", "tc_", "sq_", "mcl_"))
                   for m in mine)
    assert not {"collective_share", "device_skew"} & mine  # they move qps
    for other in cells:
        if other != CELL:
            assert not set(READERS) & {m["name"] for m in spec.metrics_for(
                other, "per_layer")}
    for rel in ("sqmcost.py", "sqmscopes.py", "sqmcontrol.py",
                "drivers/library_product_mesh.py",
                "traffic/spgemm-mesh.json", "configs/g500-sq15-2x2.json"):
        assert os.path.isfile(os.path.join(CHECKOUT, "chipbench", rel))
    for n in READERS:
        assert os.path.isfile(os.path.join(
            CHECKOUT, "chipbench", "layers", n + ".py"))


def test_the_configuration_states_its_cut_and_its_guarantees():
    spec = _spec()
    cfg = spec.config(CONFIG)
    entry = next(c for c in spec.doc["configs"] if c["name"] == CONFIG)
    assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "SCALE22RMATRMAT/btwcent49" in cfg["source"]
    assert "nnz-out/sec" in cfg["source"]
    # another source than the one-chip configuration's, a file each
    one = next(c for c in spec.doc["configs"] if c["name"] == ONE_CONFIG)
    assert one["source"] != entry["source"] and one["file"] != entry["file"]
    assert entry["reduced"] == ["scale"] == list(cfg["reduced"])
    assert cfg["reduced"]["scale"].startswith("22 (upstream's pair) -> 15")
    # the reason as far as it is known: what a job holds live, what the
    # compiler reckons for the next scale, and that no chip ran it
    assert all(w in cfg["reduced"]["scale"] for w in (
        "MEMORY", "2.09 GB of LIVE", "16.48 GB", "NOT attempted"))
    assert (cfg["grid"], cfg["scale"], cfg["edgefactor"],
            cfg["graph_seed"]) == ([2, 2], 15, 16, 1)
    assert cfg["kinds"] == [] and cfg["keep_coo"] is False
    base = spec.config("g500-s22-2x2")
    assert all(cfg[k] == base[k] for k in (
        "grid", "edgefactor", "graph_seed", "rmat"))
    assert {"operands", "unit_values", "precision", "job", "tier",
            "schedule", "graph_seed", "upload", "memory"} <= set(
        cfg["assumed"])
    assert "EQUALITY" in cfg["assumed"]["unit_values"]
    assert "draws NOTHING a job reads" in cfg["assumed"]["graph_seed"]
    assert "GATHERED" in cfg["assumed"]["schedule"]
    assert "GB" in cfg["assumed"]["memory"]
    assert {"digest", "entries", "window"} == set(cfg["guarantees"])
    assert "equality" in cfg["guarantees"]["digest"]
    assert "all four tiles" in cfg["guarantees"]["entries"]
    mix = spec.traffic(MIX)
    one_mix = spec.traffic("spgemm-batch")
    assert mix["driver"] == "library_product_mesh"
    assert all(mix[k] == one_mix[k] for k in (
        "entry", "semiring", "job", "check"))
    # the issue's slice, the one-chip cell's: some 29 whole jobs a plane
    assert mix["trace"] == one_mix["trace"] == {
        "start_s": 10.0, "seconds": 20.0}
    # the tile is the one-chip cell's: the rule picks the mix's tier for
    # it on counts alone (the heaviest tile's two stages' multiplies)
    from combblas_tpu.parallel import spgemm as S
    from combblas_tpu.semiring import PLUS_TIMES

    tile = (1 << cfg["scale"]) // 2
    assert mix["job"]["backend"] == S.JOB_BACKEND
    assert mix["job"]["tier"] == S.choose_tier_from_counts(
        PLUS_TIMES, tile, tile * tile, 2, 1.143e8, S.JOB_BACKEND,
        k_dim=tile, n_dim=tile)
    # the schedule is run_windowed's own: the job passes none
    import inspect

    sched = inspect.signature(S.run_windowed).parameters
    assert (sched["ring"].default, sched["pipeline"].default) == (False, True)
    assert not {"ring", "pipeline"} & set(
        inspect.signature(S.spgemm_job).parameters)


# --- the cell, rehearsed ----------------------------------------------------


def test_the_cell_through_the_real_command_on_four_host_devices(tmp_path):
    bench = small_benchmark(str(tmp_path), scale=9)
    # (a seed beyond 32 signed bits, as the driver's are)
    r, line = run_cell(bench, CELL, seed=2300001111, seconds=2, devices=4)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"mteps", "setup_s"} and m["mteps"] > 0
    assert line["device"]["count"] == 4
    n, rows, cols, _ = graph.rmat_graph(9, 16, 1)
    ref = sqref.SQReference(n, rows, cols)
    assert "uploaded to 2 x 2 tiles of capacity " in r.stderr
    assert "tier windowed under dot" in r.stderr
    assert (f"sq: the reference's C has {ref.C.nnz} entries of sum "
            f"{ref.products} (the largest {ref.largest}) from "
            f"{ref.products} products of {len(rows)} nonzeros, "
            f"{len(rows) // 2} undirected edges of {n} vertices") in r.stderr
    lr = n // 2
    tiles = [ref.C[i * lr:(i + 1) * lr, j * lr:(j + 1) * lr].nnz
             for i in range(2) for j in range(2)]
    assert (f"and the last job's {ref.C.nnz} stored entries, read from 4 "
            f"tiles of {' '.join(map(str, tiles))} under capacity "
            f"{max(tiles)}, against it block by block (limit: equality)"
            ) in r.stderr
    r, line = run_cell(bench, CELL, trace=1, seed=4, seconds=2, devices=4)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"deployment {CONFIG}: snapshot" in r.stderr
    m = check_line(line)
    assert {"load_s", "warmup_s", "compiles_in_window", "graph_ready_s",
            "sqm_mnnz_out_per_s"} <= set(m)
    assert m["compiles_in_window"] == 0
    assert m["sqm_mnnz_out_per_s"] > 0
    assert "boot span spgemm.job" in r.stderr
    # the device trace's readers find no device plane on a CPU (and the
    # memory reader no statistics): left out of the line, never 0
    assert not (set(READERS) - {"sqm_mnnz_out_per_s"}) & set(m)
