"""Multi-process file-substrate safety (round 17, ISSUE 15
satellites): the O_APPEND single-``write()`` contract the WAL's JSONL
rests on, and checkpoint listing/loading under a
concurrently-checkpointing sibling.

The writer children are plain interpreters (stdlib only — no jax
import) hammering the SAME file the product code reads back, so the
property is cheap enough for tier-1: two processes' interleaved
appends must produce only whole, parseable lines, with the loader's
invalid-line counter at ZERO.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from combblas_tpu.dynamic import WriteAheadLog
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import GraphEngine
from combblas_tpu.utils import checkpoint

N = 64

#: Child writer: appends ``count`` fully formed lines to one shared
#: file — each line down as ONE os.write to an O_APPEND fd, exactly the
#: product appender's contract.
_WRITER = textwrap.dedent("""
    import json, os, sys
    path, worker, count = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    fd = os.open(path, os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644)
    for k in range(count):
        seq = worker * 100000 + k
        rec = {"v": "combblas_tpu.wal/v1", "first_seq": seq,
               "last_seq": seq, "rows": [worker], "cols": [k % 64],
               "vals": [1.0], "ops": [0]}
        line = (json.dumps(rec, separators=(",", ":")) + "\\n").encode()
        n = os.write(fd, line)
        assert n == len(line)
    os.close(fd)
""")


def _run_writers(path, nworkers=2, count=400):
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WRITER, str(path), str(w),
             str(count)],
        )
        for w in range(nworkers)
    ]
    for p in procs:
        assert p.wait(timeout=120) == 0


def test_wal_concurrent_appends_only_whole_lines(tmp_path):
    """Two processes appending to ONE WAL: every line parses whole
    (the kernel's O_APPEND atomic seek+write), the loader's invalid
    counter is zero, and replay sees every record."""
    path = tmp_path / "wal.jsonl"
    _run_writers(path)
    wal = WriteAheadLog(str(path))
    batches = wal.replay()
    assert wal.invalid_lines == 0
    assert sum(len(b) for b in batches) == 800
    # and the product appender interoperates on the same file
    wal.append(500000, [1], [2], [1.0], [0])
    assert wal.position() == 500000
    wal.close()


@pytest.fixture(scope="module")
def grid():
    return Grid.make(1, 1)


def _coo(seed, n=N, m=300):
    r = np.random.default_rng(seed)
    rows = r.integers(0, n, m)
    cols = r.integers(0, n, m)
    return (
        np.concatenate([rows, cols]), np.concatenate([cols, rows])
    )


def test_list_snapshots_ignores_inflight_tmp(tmp_path, grid):
    rows, cols = _coo(1)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",))
    p = str(tmp_path / checkpoint.snapshot_name(3))
    checkpoint.save_version(p, eng.version)
    # a sibling's in-flight atomic write: half an npz under .tmp names
    open(p + ".tmp", "wb").write(b"partial")
    open(str(tmp_path / "ckpt-000000000009.npz.tmp"), "wb").write(b"x")
    assert checkpoint.list_snapshots(str(tmp_path)) == [p]
    v, path = checkpoint.load_latest_version(str(tmp_path), grid)
    assert path == p


def test_vanished_snapshot_retries_fresh_listing(tmp_path, grid,
                                                 monkeypatch):
    """ISSUE 15 satellite: a snapshot pruned by a sibling between
    listing and open is NOT a SnapshotError — the loader re-lists
    once and finds the sibling's newer snapshot (no spurious
    rejected-counter, no warning)."""
    import warnings

    rows, cols = _coo(2)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",))
    old = str(tmp_path / checkpoint.snapshot_name(3))
    newer = str(tmp_path / checkpoint.snapshot_name(9))
    checkpoint.save_version(old, eng.version)

    real_load = checkpoint.load_version
    state = {"raced": False}

    def racing_load(path, grid_, **kw):
        if path == old and not state["raced"]:
            # the sibling checkpoints seq 9 and prunes seq 3 in the
            # window between our listdir and our open
            state["raced"] = True
            checkpoint.save_version(newer, eng.version)
            os.unlink(old)
        return real_load(path, grid_, **kw)

    monkeypatch.setattr(checkpoint, "load_version", racing_load)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any fallback warning fails
        v, path = checkpoint.load_latest_version(str(tmp_path), grid)
    assert state["raced"] and path == newer
    assert checkpoint.snapshot_seq(path) == 9
