"""CPU rehearsals of the readers PR 23 added, through the real command
at scale 9: the six program-side metrics (the engine's parts of
``execute``, the byte counters, the gap between batches) are printed
under ``rehearsal.`` and add up; the device-trace ones are left out on
a CPU, which has no device plane."""

import pytest

from rehearse import check_line, run_cell, small_benchmark

PROGRAM_SIDE = {"launch_ms", "readback_ms", "to_global_ms",
                "readback_mb_per_query", "scatter_copied_mb",
                "batch_gap_ms"}
DEVICE_TRACE = {"bfs_gather_share", "bfs_level_ms", "k2_gather_share",
                "k2_level_ms", "k2_parents_ms"}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return small_benchmark(str(tmp_path_factory.mktemp("parts")))


@pytest.mark.parametrize("cell,devices", [
    ("g500-s20.bfs-sat", 1), ("g500-s22x4.bfs-sat", 4),
])
def test_traced_served_cell_prints_the_program_side_metrics(
        bench, cell, devices):
    r, line = run_cell(bench, cell, trace=1, devices=devices)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert PROGRAM_SIDE <= set(m), sorted(PROGRAM_SIDE - set(m))
    assert not DEVICE_TRACE & set(m)
    # the parts lie inside the stage they split (medians of one set of
    # batches: the sum of medians stays under the median of sums plus
    # the device part, which no reader prints on its own)
    assert 0 <= m["launch_ms"] < m["execute_ms"]
    assert 0 <= m["readback_ms"] < m["execute_ms"]
    assert 0 <= m["to_global_ms"] < m["execute_ms"]
    assert m["batch_gap_ms"] >= 0
    # a full 16-wide batch reads back parents and levels, int32 [n, 16]
    # at scale 9: 2 x 512 x 16 x 4 B over 16 requests; the drain's
    # partial batches (same bytes, fewer requests) only raise it
    assert m["readback_mb_per_query"] >= 2 * 512 * 16 * 4 / 16 / 1e6
    assert m["readback_mb_per_query"] < 2 * 512 * 16 * 4 / 1e6
    # on a CPU the result is row-major, so every lane is a copy
    assert m["scatter_copied_mb"] == pytest.approx(
        2 * 512 * 4 * 16 / 1e6, rel=0.5)
    assert m["compiles_in_window"] == 0  # publishing op names is set-up


def test_traced_library_cell_survives_publishing_its_op_names(bench):
    r, line = run_cell(bench, "g500-s20.k2-batch", trace=1)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert not (PROGRAM_SIDE | DEVICE_TRACE) & set(m)
    assert m["compiles_in_window"] == 0 and m["mteps_aggregate"] > 0
