"""Every ``per_layer`` entry of ``BENCHMARK.json`` against the contract.

``tests/chipbench/test_chipbench_scopes.py::
test_new_per_layer_entries_follow_the_contract`` holds PR 23's eleven
entries to it and pins them as the file's LAST (line 221:
``names[-len(NEW):] == NEW``), which no later entry can leave true: the
driver takes a new entry only at the end.  That file is the benchmark's
(only a ``benchmark`` PR may edit it), so ``conftest.py`` marks its
eleven cases expected-to-fail and this file holds every assertion they
made, the pin turned into the order check, for EVERY entry."""

import os

import pytest

from chipbench.spec import CHECKOUT, Spec

BENCH = os.path.join(CHECKOUT, "BENCHMARK.json")

#: PR 23's eleven, PR 34's six, PR 37's six, PR 40's seven, PR 44's
#: seven, PR 48's ten and PR 50's seven, each run in the order its issue
#: gave
RUNS = [
    ["launch_ms", "readback_ms", "to_global_ms", "readback_mb_per_query",
     "scatter_copied_mb", "batch_gap_ms", "bfs_gather_share",
     "bfs_level_ms", "k2_gather_share", "k2_level_ms", "k2_parents_ms"],
    ["graph_ready_s", "upload_s", "boot_trace_s", "boot_fetch_s",
     "boot_probe_s", "boot_unspanned_s"],
    ["tc_device_ms", "tc_pack_ms", "tc_harvest_ms", "tc_pairs_per_edge",
     "tc_hbm_share", "tc_hbm_peak_gb"],
    ["sq_device_ms", "sq_dot_ms", "sq_extract_ms", "sq_host_gap_ms",
     "sq_mnnz_out_per_s", "sq_hbm_share", "sq_hbm_peak_gb"],
    ["mcl_device_ms", "mcl_expand_ms", "mcl_select_ms", "mcl_host_gap_ms",
     "mcl_iters", "mcl_hbm_share", "mcl_hbm_peak_gb"],
    ["sqm_device_ms", "sqm_dot_ms", "sqm_extract_ms", "sqm_exchange_ms",
     "sqm_host_gap_ms", "sqm_collective_share", "sqm_device_skew",
     "sqm_mnnz_out_per_s", "sqm_hbm_share", "sqm_hbm_peak_gb"],
    ["ell_mslots_per_batch", "ell_skipped_share", "ell_ns_per_index",
     "open_mslots_per_query", "open_wave_ns_per_slot", "cc_mslots_per_job",
     "cc_ns_per_index"],
]


def _names():
    return [m["name"] for m in Spec(BENCH).doc["per_layer"]]


@pytest.mark.parametrize("name", _names())
def test_a_per_layer_entry_follows_the_contract(name):
    spec = Spec(BENCH)
    m = {x["name"]: x for x in spec.doc["per_layer"]}[name]
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    # the layer is one PERF.md section 3 names, letter for letter
    with open(os.path.join(CHECKOUT, "PERF.md")) as f:
        assert f"| {m['layer']} |" in f.read()
    # each cell that reports it reports the end-to-end metric it moves;
    # an entry with no list is in every cell, so that metric must be too
    cells = [w["name"] for w in spec.doc["workloads"]]
    e2e = {x["name"]: x for x in spec.doc["end_to_end"]}[m["moves"]]
    mine = m.get("workloads", cells)
    assert mine and set(mine) <= set(e2e.get("workloads", cells))
    # and its reader is a file of its own
    assert callable(spec.load_module("layers", name).read)


@pytest.mark.parametrize("run", RUNS, ids=["pr23", "pr34", "pr37", "pr40", "pr44", "pr48", "pr50"])
def test_appended_entries_keep_their_issues_order(run):
    names = _names()
    assert [n for n in names if n in run] == run
    assert len(set(names)) == len(names)


def test_each_run_was_appended_after_the_run_before_it():
    """A run's first entry comes after the last of the run before: where
    the driver takes new entries, at the end of what was there then."""
    names = _names()
    for before, after in zip(RUNS, RUNS[1:]):
        assert names.index(before[-1]) < names.index(after[0])


def test_the_boot_metrics_keep_their_order_and_are_in_every_cell():
    spec = Spec(BENCH)
    boot = [m for m in spec.doc["per_layer"] if m["name"] in RUNS[1]]
    assert [m["name"] for m in boot] == RUNS[1]
    for m in boot:
        assert "workloads" not in m
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "s", "lower", "program_span", "setup_s")
    assert [m["layer"] for m in boot] == [
        "set-up", "set-up", "compiler / cache", "compiler / cache",
        "set-up", "set-up"]
    for w in spec.doc["workloads"]:
        mine = {m["name"] for m in spec.metrics_for(w["name"], "per_layer")}
        assert set(RUNS[1]) <= mine
