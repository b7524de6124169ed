"""Helpers of the CPU rehearsals: a BENCHMARK.json of one's own in a
temporary directory (scale 9, short slices), and one run of the real
command against it in a fresh process."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def small_benchmark(root, scale=9, extra=None):
    """Copy the repo's BENCHMARK.json, configurations and mixes into
    ``root`` with the graphs cut to ``scale`` and the traffic to what a
    CPU finishes in seconds.  Drivers and readers are found in the repo.
    ``extra(doc)`` may add entries before the file is written."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["paths"] = ["chipbench"]
    for c in doc["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        cfg["scale"] = scale
        path = os.path.join(root, c["file"])
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(cfg, f)
    os.makedirs(os.path.join(root, "chipbench", "traffic"), exist_ok=True)
    short = {"trace": {"start_s": 0.5, "seconds": 1.0}}
    for mix, over in (("bfs-sat", short),
                      ("bfs-open", dict(short, rate=25.0)),
                      ("k2-batch", dict(short, width=16))):
        with open(os.path.join(REPO, "chipbench", "traffic",
                               mix + ".json")) as f:
            m = json.load(f)
        m.update(over)
        with open(os.path.join(root, "chipbench", "traffic",
                               mix + ".json"), "w") as f:
            json.dump(m, f)
    if extra is not None:
        extra(doc)
    bench = os.path.join(root, "BENCHMARK.json")
    with open(bench, "w") as f:
        json.dump(doc, f)
    return bench


def run_cell(bench, workload, trace=0, seed=3, seconds=2, env=None,
             devices=1):
    """The real command, in a fresh process, as the driver runs it (plus
    ``--bench``).  Returns (CompletedProcess, last stdout line parsed or
    None)."""
    env = dict(os.environ if env is None else env)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    r = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--bench", bench,
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    lines = r.stdout.strip().splitlines()
    return r, (json.loads(lines[-1]) if lines else None)


def check_line(line, platform="cpu"):
    """The last line's shape, as the driver reads it."""
    assert set(line) - {"breakdown"} == {
        "correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["platform"] == platform
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
        # a CPU number never carries a device metric's name
        assert name.startswith("rehearsal.")
    return {k.split(".", 1)[1]: v["value"]
            for k, v in line["metrics"].items()}
