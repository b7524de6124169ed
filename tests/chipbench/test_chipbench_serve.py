"""CPU rehearsals of the two served drivers, end to end through the real
command at scale 9 under ``JAX_PLATFORMS=cpu`` given by name."""

import pytest

from rehearse import check_line, run_cell, small_benchmark


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return small_benchmark(str(tmp_path_factory.mktemp("served")))


def test_closed_loop_cell_and_the_snapshot(bench):
    r, line = run_cell(bench, "g500-s20.bfs-sat")
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"qps", "setup_s"} and m["qps"] > 0
    assert "deployment g500-s20-1x1: built" in r.stderr
    # the second run of the configuration loads what the first one built
    r, line = run_cell(bench, "g500-s20.bfs-sat", trace=1, seed=4)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "deployment g500-s20-1x1: snapshot" in r.stderr
    m = check_line(line)
    assert {"execute_ms", "scatter_ms", "sat_lane_fill", "load_s",
            "warmup_s", "compiles_in_window"} <= set(m)
    assert m["compiles_in_window"] == 0
    assert m["sat_lane_fill"] > 90
    # no device plane on a CPU: no device-trace metric, no busy time
    assert not {"bfs_device_ms", "hbm_share"} & set(m)
    assert "busy_s" not in line["device"]


def test_open_loop_cell(bench):
    r, line = run_cell(bench, "g500-s20.bfs-open", seconds=3)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"p50_ms", "p95_ms", "setup_s"}
    assert 0 < m["p50_ms"] <= m["p95_ms"]
    assert line["attempted"] == int(25.0 * 3)  # the schedule's fixed count
    r, line = run_cell(bench, "g500-s20.bfs-open", trace=1, seconds=3)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert {"gen_late_ms", "gen_late_max_ms", "queue_wait_ms", "lane_fill",
            "open_execute_ms", "p95_pooled_ms"} <= set(m)
    assert "median of 5 blocks" in r.stderr  # the mix's tail_blocks
    assert m["gen_late_max_ms"] >= m["gen_late_ms"] >= 0
    assert 0 < m["lane_fill"] <= 100


def test_mesh_cell_on_four_virtual_devices(bench):
    r, line = run_cell(bench, "g500-s22x4.bfs-sat", devices=4)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"qps", "setup_s"}
    assert line["device"]["count"] == 4
    # fewer devices than the cell asks for: no result
    r, line = run_cell(bench, "g500-s22x4.bfs-sat", devices=2)
    assert r.returncode != 0 and line is None
    assert "needs 4 chips" in r.stderr
