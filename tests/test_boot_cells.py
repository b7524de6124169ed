"""The two cases of ``tests/chipbench/test_chipbench_cc.py`` that pin the
CC cell's per-layer metrics to exactly three (``conftest.py`` says why
that file stands as it is and they are expected to fail), with the sets
turned into subsets: every other assertion they made, and on the traced
rehearsal the six set-up metrics a library cell reads too.  Since PR 50
the cell is in two per-layer lists of its own (``ELL``: the work of its
sweeps), which the same two cases hold; and the case of
``test_chipbench_bc.py`` that pins the lists the BC cell is in, PR 50's
three added, likewise."""

import os
import sys

from chipbench.spec import CHECKOUT, Spec

CELL, CONFIG, MIX = "g500-s20cc.cc-batch", "g500-s20-cc-1x1", "cc-batch"
BC_CELL, BC_CONFIG = "g500-s20bc.bc-sat", "g500-s20-bc-1x1"
READERS = ["cc_device_ms", "cc_round_ms", "cc_rounds", "cc_spmv_share",
           "cc_hook_share", "cc_hbm_share"]
EVERY_CELL = {"compiles_in_window", "load_s", "warmup_s"}
BOOT = {"graph_ready_s", "upload_s", "boot_trace_s", "boot_fetch_s",
        "boot_probe_s", "boot_unspanned_s"}
#: PR 50: what the cell's sweeps gathered, and what an index cost
ELL = ["cc_mslots_per_job", "cc_ns_per_index"]


def _rehearse():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "chipbench"))
    try:
        import rehearse
    finally:
        sys.path.pop(0)
    return rehearse


def test_the_cc_cell_is_appended_and_its_readers_wait_for_a_benchmark_pr():
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
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
    # it joined one list, after the cell that was there; PR 50's two
    # lists are its own
    joined = [m for sec in ("end_to_end", "per_layer")
              for m in spec.doc[sec] if CELL in m.get("workloads", ())]
    assert [m["name"] for m in joined] == ["mteps"] + ELL
    at = joined[0]["workloads"].index
    assert at("g500-s20.k2-batch") < at(CELL)
    assert all(m["workloads"] == [CELL] for m in joined[1:])
    # and reports the per-layer metrics every cell reports, and no other
    mine = {m["name"] for m in spec.metrics_for(CELL, "per_layer")}
    assert EVERY_CELL <= mine
    assert all("workloads" not in m
               for m in spec.metrics_for(CELL, "per_layer")
               if m["name"] not in ELL)
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


def test_the_cc_cell_through_the_real_command(tmp_path):
    reh = _rehearse()
    bench = reh.small_benchmark(str(tmp_path))
    # (a seed beyond 32 signed bits, as the driver's are)
    r, line = reh.run_cell(bench, CELL, seed=2300001111, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = reh.check_line(line)
    assert set(m) == {"mteps", "setup_s"} and m["mteps"] > 0
    assert "against the reference on all entries" in r.stderr
    assert "cc: every job 4 rounds and 1 jumps" in r.stderr
    r, line = reh.run_cell(bench, CELL, trace=1, seed=4, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"deployment {CONFIG}: snapshot" in r.stderr
    m = reh.check_line(line)
    # (no device plane here: the trace's reader of the two finds nothing)
    assert set(m) == EVERY_CELL | BOOT | {"cc_mslots_per_job"}
    assert m["compiles_in_window"] == 0
    # a library cell's boot: the restore is the program's, the warm-up
    # call the benchmark's (outside every span), the probe a top-level
    # span of the program's first traced call
    assert 0 < m["upload_s"] < m["graph_ready_s"] <= m["load_s"]
    assert m["boot_probe_s"] > 0 and m["boot_trace_s"] > 0
    assert m["boot_fetch_s"] <= m["warmup_s"] < m["boot_unspanned_s"]
    assert "boot span obs.opnames.publish" in r.stderr
    # the kind's own readings are logged, not in the line: the counter's
    # on any platform, the device trace's only where there is a device plane
    logged = dict(ln.split("layer ", 1)[1].split(": ", 1)
                  for ln in r.stderr.splitlines() if "layer cc_" in ln)
    assert list(logged) == READERS
    assert float(logged.pop("cc_rounds")) == 4.0
    assert set(logged.values()) == {"nothing to read"}


def test_the_bc_cell_is_in_the_lists_it_joined_and_pr_50_s_three():
    """``test_chipbench_bc.py::
    test_the_cell_is_appended_and_its_readers_wait_for_a_benchmark_pr``
    holds the lists the BC cell is in to the eleven it joined; PR 50
    appended three (the work of its sweeps), so that case is expected to
    fail (``conftest.py``) and its assertions are held here, the set
    three larger."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "chipbench"))
    try:
        import test_chipbench_bc as bc
    finally:
        sys.path.pop(0)
    spec = Spec(os.path.join(CHECKOUT, "BENCHMARK.json"))
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert not set(bc.READERS) & set(names)
    assert list(spec.load_module(
        "drivers", "serve_closed_bc").LAYERS) == bc.READERS
    cells = [w["name"] for w in spec.doc["workloads"]]
    assert cells.index(bc.K3_CELL) < cells.index(bc.CELL)
    cell = spec.cell(bc.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        bc.CONFIG, bc.MIX, 1)
    reported = {m["name"] for m in spec.metrics_for(bc.CELL, "end_to_end")}
    assert reported == {"qps", "setup_s"}
    mine = {m["name"] for m in spec.metrics_for(bc.CELL, "per_layer")}
    assert bc.SHARED <= mine
    assert not any(m.startswith(("bfs_", "k2_")) for m in mine)
    joined = [m for sec in ("end_to_end", "per_layer")
              for m in spec.doc[sec] if bc.CELL in m.get("workloads", ())]
    assert {m["name"] for m in joined} == bc.SHARED | {"qps"} | {
        "ell_mslots_per_batch", "ell_skipped_share", "ell_ns_per_index"}
    for m in joined:
        at = m["workloads"].index
        assert at(bc.K3_CELL) < at(bc.CELL), m["name"]
