"""CPU rehearsals: the library driver, the platform gate, and a cell, a
configuration, a mix and a per-layer metric added as files alone."""

import json
import os

import pytest

from rehearse import REPO, check_line, run_cell, small_benchmark


def test_library_batch_cell(tmp_path):
    bench = small_benchmark(str(tmp_path))
    r, line = run_cell(bench, "g500-s20.k2-batch")
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert set(m) == {"mteps", "setup_s"} and m["mteps"] > 0
    assert line["attempted"] % 16 == 0  # whole batches only
    r, line = run_cell(bench, "g500-s20.k2-batch", trace=1)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert {"load_s", "warmup_s", "compiles_in_window",
            "mteps_aggregate"} <= set(m)
    assert m["mteps_aggregate"] > 0
    assert "Medges/s by batch" in r.stderr
    assert "k2_device_ms" not in m  # no device plane on a CPU


def test_no_tpu_and_no_cpu_by_name_exits_nonzero_with_no_result(
        tmp_path, monkeypatch, capsys):
    """This process's backend is the CPU (conftest).  Without
    ``JAX_PLATFORMS=cpu`` BY NAME that is not a rehearsal: the command
    exits non-zero and prints nothing."""
    from chipbench import run

    bench = small_benchmark(str(tmp_path))
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as exc:
        run.main(["--bench", bench, "--workload", "g500-s20.bfs-sat",
                  "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert "not 'tpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_alone_in_a_directory_it_prints_no_result(tmp_path):
    """With only BENCHMARK.json and the files under ``paths`` beside it
    (no program), the command exits non-zero with no result line."""
    import shutil
    import subprocess
    import sys

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(REPO, "chipbench"), tmp_path / "chipbench",
        ignore=shutil.ignore_patterns(".cache", "__pycache__"),
    )
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "g500-s20.bfs-sat", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_a_cell_is_added_as_files_and_one_entry_each(tmp_path):
    """A later PR's cell: a new configuration file, a new mix file, a new
    per-layer reader, and one new BENCHMARK.json entry for each — no
    existing file edited, the drivers found in the repo."""
    root = str(tmp_path)

    def extra(doc):
        with open(os.path.join(REPO, "chipbench", "configs",
                               "g500-s20-1x1.json")) as f:
            cfg = json.load(f)
        cfg.update(scale=8, kinds=["bfs"], keep_coo=False,
                   lane_widths=[1, 8])
        with open(os.path.join(root, "chipbench", "configs",
                               "tiny-s8.json"), "w") as f:
            json.dump(cfg, f)
        with open(os.path.join(root, "chipbench", "traffic",
                               "bfs-trickle.json"), "w") as f:
            json.dump({"driver": "serve_open", "kind": "bfs", "rate": 10.0,
                       "drain_s": 30.0, "check": {"exact": 1, "tree": 2},
                       "trace": {"start_s": 0.5, "seconds": 1.0}}, f)
        os.makedirs(os.path.join(root, "chipbench", "layers"))
        with open(os.path.join(root, "chipbench", "layers",
                               "batches_run.py"), "w") as f:
            f.write("def read(ctx):\n"
                    "    return ctx['stats']['batches']\n")
        doc["configs"].append({
            "name": "tiny-s8", "source": cfg["source"],
            "file": "chipbench/configs/tiny-s8.json",
            "reduced": ["scale"], "why": "a test's own deployment"})
        doc["workloads"].append({
            "name": "tiny.bfs-trickle", "config": "tiny-s8",
            "traffic": "bfs-trickle", "chips": 1, "why": "a test's cell"})
        # the new cell's name goes into the list of every metric it
        # reports: the end-to-end ones and the per-layer ones they move
        for m in doc["end_to_end"]:
            if m["name"] in ("p50_ms", "p95_ms"):
                m["workloads"].append("tiny.bfs-trickle")
        for m in doc["per_layer"]:
            if m["moves"] in ("p50_ms", "p95_ms"):
                m["workloads"].append("tiny.bfs-trickle")
        doc["per_layer"].append({
            "name": "batches_run", "unit": "count", "better": "lower",
            "source": "program_counter", "layer": "scheduler / batcher",
            "moves": "p50_ms", "workloads": ["tiny.bfs-trickle"]})

    bench = small_benchmark(root, extra=extra)
    r, line = run_cell(bench, "tiny.bfs-trickle", seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert set(check_line(line)) == {"p50_ms", "p95_ms", "setup_s"}
    assert line["attempted"] == 20
    assert "deployment tiny-s8: built" in r.stderr
    r, line = run_cell(bench, "tiny.bfs-trickle", trace=1, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    m = check_line(line)
    assert m["batches_run"] >= 1  # the dropped-in reader was found
    assert "queue_wait_ms" in m
    # and the new metric exists only in the cell that lists it
    r, line = run_cell(bench, "g500-s20.bfs-open", trace=1, seconds=2)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "batches_run" not in check_line(line)
