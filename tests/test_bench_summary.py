"""Bench summary-line contract (ISSUE 3 satellites 1-2 + CI guard).

The driver's end-of-round capture takes the LAST stdout line; the r05
artifact ended up ``parsed: null`` because tail truncation of the giant
per-run record ate the headline.  The contract under test: ``bench.py``'s
final line is a COMPACT parseable JSON summary carrying ``value``,
``median``, ``warning``, ``rc``, and the same object is mirrored to
``BENCH_SUMMARY.json``.
"""

import importlib.util
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: The summary line's required keys — the satellite-1 contract that the
#: CI guard (this file) pins down.
REQUIRED_KEYS = {"summary", "metric", "value", "median", "warning", "rc"}


@pytest.fixture(scope="module")
def benchmod():
    spec = importlib.util.spec_from_file_location(
        "benchmod_under_test", os.path.join(REPO, "bench.py")
    )
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


def test_emit_summary_is_parseable_with_required_keys(
    benchmod, tmp_path, monkeypatch
):
    monkeypatch.setenv(
        "BENCH_SUMMARY_PATH", str(tmp_path / "BENCH_SUMMARY.json")
    )
    official = {
        "metric": "graph500_bfs_rmat_scale20_1chip_MTEPS",
        "value": 14.5,
        "batch_median_mteps": 246.4,
        "warning": None,
        "runs": [{"huge": "x" * 10000}],  # the giant record is NOT copied
    }
    buf = io.StringIO()
    with redirect_stdout(buf):
        benchmod.emit_summary(official)
    lines = buf.getvalue().strip().splitlines()
    s = json.loads(lines[-1])  # the FINAL line parses alone
    assert REQUIRED_KEYS <= set(s)
    assert s["value"] == 14.5
    assert s["median"] == 246.4
    assert s["rc"] == 0
    assert len(lines[-1]) < 500, "summary must be truncation-proof small"
    sidecar = json.loads((tmp_path / "BENCH_SUMMARY.json").read_text())
    assert sidecar == s


def test_emit_summary_survives_unwritable_sidecar(benchmod, monkeypatch):
    monkeypatch.setenv(
        "BENCH_SUMMARY_PATH", "/nonexistent-dir/BENCH_SUMMARY.json"
    )
    buf = io.StringIO()
    with redirect_stdout(buf):
        benchmod.emit_summary({"value": 1.0}, rc=1)
    s = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert s["rc"] == 1 and "summary_write_error" in s


def test_rc_is_nonzero_when_nothing_was_measured(
    benchmod, capsys, tmp_path, monkeypatch
):
    """D.4: a record whose every run carries "error" gives rc != 0 —
    and so does one measured on a platform that was not asked for."""
    monkeypatch.setenv(
        "BENCH_SUMMARY_PATH", str(tmp_path / "BENCH_SUMMARY.json")
    )
    failed = [{"mteps": 0.0, "error": "no chip"}] * 2
    official = benchmod.emit(failed, [], 1.0, {}, 0.0)
    assert "error" in official
    assert benchmod.emit_summary(official) == 1
    s = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert s["rc"] == 1 and s["value"] == 0.0 and s["warning"]
    # measured, but on a CPU nobody asked for by name: not a chip number
    cpu = {"platform": "cpu", "device_kind": "cpu", "n_devices": 1}
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    official = benchmod.emit([{"mteps": 5.0, **cpu}], [], 1.0, {}, 0.0)
    assert official["metric"].endswith("_cpu_MTEPS")
    assert benchmod.emit_summary(official) == 1
    # the same record under an explicit JAX_PLATFORMS=cpu passes, and a
    # TPU record is the only one named "1chip"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    official = benchmod.emit([{"mteps": 5.0, **cpu}], [], 1.0, {}, 0.0)
    assert benchmod.emit_summary(official) == 0
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 1}
    official = benchmod.emit([{"mteps": 5.0, **tpu}], [], 1.0, {}, 0.0)
    capsys.readouterr()
    assert official["metric"].endswith("_1chip_MTEPS")
    assert benchmod.emit_summary(official) == 0
    s = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert s["platform"] == "tpu" and s["device_kind"] == "TPU v5 lite"


def test_emit_reports_median_and_spread(benchmod, capsys):
    runs = [
        {"mteps": 90.0}, {"mteps": 100.0}, {"mteps": 130.0},
    ]
    out = benchmod.emit(runs, [], 1.0, {}, 0.0)
    capsys.readouterr()
    assert out["batch_median_mteps"] == 100.0
    sp = out["repeats_spread"]
    assert sp["min"] == 90.0 and sp["max"] == 130.0
    assert sp["rel_spread"] == pytest.approx(0.4)


def test_spgemm_bench_summary_fields():
    """The SpGEMM bench line also satisfies the driver's minimal
    contract (parseable, has "value") — pinned here since the perf
    acceptance reads it."""
    # static check on the emitted dict keys (no run): the bench builds
    # its JSON inline, so just assert the file mentions the fields the
    # driver parses
    src = open(os.path.join(REPO, "benchmarks", "spgemm_bench.py")).read()
    for field in ('"value"', '"out_nnz"', '"overflow"', '"tier"'):
        assert field in src, field


@pytest.mark.slow
def test_bench_end_to_end_summary_line(tmp_path):
    """Full bench.py subprocess at a toy scale: stdout ends with the
    parseable summary line and BENCH_SUMMARY.json is written."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        BENCH_SCALE="8", BENCH_NROOTS="8", BENCH_REPEATS="1",
        BENCH_SEQ_ROOTS="0", BENCH_VALIDATE="0", BENCH_DRAIN_S="0",
        BENCH_BUDGET_S="600",
        BENCH_SUMMARY_PATH=str(tmp_path / "BENCH_SUMMARY.json"),
    )
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900,
    )
    lines = [l for l in r.stdout.strip().splitlines() if l.strip()]
    assert lines, r.stderr[-2000:]
    s = json.loads(lines[-1])
    assert REQUIRED_KEYS <= set(s), s
    assert s["rc"] == 0, (s, r.stderr[-2000:])
    assert s["value"] > 0
    # the full record is on an EARLIER line
    full = json.loads(lines[-2])
    assert "runs" in full and full["value"] == s["value"]
    sidecar = json.loads((tmp_path / "BENCH_SUMMARY.json").read_text())
    assert sidecar == s


def test_pool_summary_honors_contract(tmp_path, monkeypatch):
    """Round 14: the standalone BENCH_SERVE_POOL scenario emits the
    SAME final-line contract (plus the per-tenant breakdown) without
    going through bench.py's wrapper."""
    spec = importlib.util.spec_from_file_location(
        "serve_bench_under_test",
        os.path.join(REPO, "benchmarks", "serve_bench.py"),
    )
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    monkeypatch.setenv(
        "BENCH_SUMMARY_PATH", str(tmp_path / "BENCH_SUMMARY.json")
    )
    out = {
        "metric": "serve_pool_throughput",
        "value": 1234.5,
        "p50_ms": 12.0,
        "ok": True,
        "per_tenant": {"t0": {"queries": 10, "rejected": 0}},
        "obs_jsonl": "x" * 10000,  # giant fields are NOT copied
    }
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = m._emit_pool_summary(out)
    assert rc == 0
    line = buf.getvalue().strip().splitlines()[-1]
    s = json.loads(line)
    assert REQUIRED_KEYS <= set(s)
    assert s["value"] == 1234.5
    assert s["median"] == 12.0
    assert s["per_tenant"]["t0"]["queries"] == 10
    mirror = json.load(open(tmp_path / "BENCH_SUMMARY.json"))
    assert mirror == s
    # a failed gate maps to rc=1 (the driver's capture semantics)
    out["ok"] = False
    with redirect_stdout(io.StringIO()):
        assert m._emit_pool_summary(out) == 1
