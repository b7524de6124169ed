"""Durability & self-healing (round 16, ISSUE 14): the write-ahead
log, crash recovery, replica supervision and write-home failover.

The load-bearing property here is CRASH-RECOVERY BIT-EXACTNESS: for a
crash at every append/merge/checkpoint boundary (torn final WAL line
included), ``recover_version`` = latest valid snapshot + WAL-suffix
replay must be ``to_host_coo()``-equal with a never-crashed engine
that merged the same acknowledged ops — and no acknowledged write may
be lost.  Tier-1 runs the boundary sweep on a 1x1 grid plus one 2x4
representative; the threaded kill-storm soak is ``slow``.
"""

import json
import os
import time

import numpy as np
import pytest

from combblas_tpu.dynamic import (
    DeltaBatch,
    RecoveryError,
    WriteAheadLog,
    apply_delta,
    open_wal,
    recover_version,
)
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import (
    FleetRouter,
    GraphEngine,
    ServeConfig,
    Server,
)
from combblas_tpu.serve.fleet import ReplicaDeadError
from combblas_tpu.utils import checkpoint

N = 64


def _coo(seed, n=N, m=300):
    r = np.random.default_rng(seed)
    rows = r.integers(0, n, m)
    cols = r.integers(0, n, m)
    return (
        np.concatenate([rows, cols]), np.concatenate([cols, rows])
    )


def _absent_pairs(rows, cols, k, n=N):
    present = set(zip(rows.tolist(), cols.tolist()))
    out = []
    for i in range(n):
        for j in range(i + 1, n):
            if (i, j) not in present and (j, i) not in present:
                out.append((i, j))
                if len(out) >= k:
                    return out
    return out


def _edges(version):
    return version.E.to_host_coo()


def _assert_bit_exact(va, vb):
    for x, y in zip(_edges(va), _edges(vb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module")
def grid():
    return Grid.make(1, 1)


# --- WAL unit behavior -------------------------------------------------------


def test_wal_roundtrip_position_and_resume(tmp_path):
    """Append -> replay round-trips ops and seq ranges; a reopened log
    resumes the frontier (the promotion / recovery lineage)."""
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    assert wal.position() == -1
    wal.append(0, [3, 9], [9, 3], [1.0, 2.5], [0, 2])
    wal.append(2, [5], [6], [1.0], [1])
    assert wal.position() == 2
    batches = wal.replay()
    assert [(b.first_seq, b.last_seq) for b in batches] == [(0, 1), (2, 2)]
    np.testing.assert_array_equal(batches[0].rows, [3, 9])
    np.testing.assert_array_equal(batches[0].vals,
                                  np.asarray([1.0, 2.5], np.float32))
    np.testing.assert_array_equal(batches[0].ops, [0, 2])
    # suffix replay masks past a snapshot frontier mid-record (the
    # record's seq range is metadata; the ops are sliced)
    suffix = wal.replay(after_seq=0)
    assert [(b.first_seq, b.last_seq) for b in suffix] == [(0, 1), (2, 2)]
    np.testing.assert_array_equal(suffix[0].rows, [9])
    assert len(suffix[0]) == 1
    wal.close()
    # reopen: the frontier survives the process
    wal2 = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    assert wal2.position() == 2
    wal2.close()


def test_wal_torn_final_line_tolerated(tmp_path):
    """The expected crash artifact: a torn (partial) FINAL line is
    skipped — earlier records replay intact."""
    path = str(tmp_path / "wal.jsonl")
    wal = WriteAheadLog(path)
    wal.append(0, [1], [2], [1.0], [0])
    wal.close()
    with open(path, "a") as f:  # a write() died mid-line
        f.write('{"v": "combblas_tpu.wal/v1", "first_seq": 1, "la')
    wal2 = WriteAheadLog(path)
    batches = wal2.replay()
    assert len(batches) == 1 and batches[0].last_seq == 0
    assert wal2.invalid_lines == 1
    wal2.close()


def test_wal_interior_damage_skipped_not_poisoning(tmp_path):
    path = str(tmp_path / "wal.jsonl")
    with open(path, "w") as f:
        f.write('{"v": "combblas_tpu.wal/v1", "first_seq": 0, '
                '"last_seq": 0, "rows": [1], "cols": [2], '
                '"vals": [1.0], "ops": [0]}\n')
        f.write("garbage not json\n")
        f.write('{"v": "some.other/v9", "first_seq": 1, "last_seq": 1, '
                '"rows": [9], "cols": [9], "vals": [1.0], "ops": [0]}\n')
        f.write('{"v": "combblas_tpu.wal/v1", "first_seq": 1, '
                '"last_seq": 1, "rows": [4], "cols": [5], '
                '"vals": [1.0], "ops": [0]}\n')
    wal = WriteAheadLog(path)
    batches = wal.replay()
    assert [(b.first_seq, b.last_seq) for b in batches] == [(0, 0), (1, 1)]
    assert wal.invalid_lines == 2  # garbage + wrong schema
    wal.close()


def test_wal_truncate_keeps_suffix_and_frontier(tmp_path):
    """Checkpoint truncation drops the replayed prefix atomically and
    a FULLY truncated log still remembers its seqno frontier (the
    mark record) — sequence numbers must never restart."""
    path = str(tmp_path / "wal.jsonl")
    wal = WriteAheadLog(path)
    wal.append(0, [1], [2], [1.0], [0])
    wal.append(1, [3], [4], [1.0], [0])
    assert wal.truncate(0) == 1
    assert [b.last_seq for b in wal.replay()] == [1]
    assert wal.position() == 1
    assert wal.truncate(1) == 1  # now empty of data records
    assert wal.replay() == []
    assert wal.position() == 1
    wal.close()
    wal2 = WriteAheadLog(path)  # reopen: frontier still 1
    assert wal2.position() == 1
    wal2.close()
    assert not os.path.exists(path + ".tmp")


def test_wal_later_lines_win_on_reused_seqs(tmp_path):
    """Review finding (round 16): an append whose fsync raised AFTER
    the line reached disk was ROLLED BACK and rejected — the caller's
    retry legitimately reuses its sequence numbers.  Replay must apply
    the LATER (acknowledged) record, never the rejected one."""
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    wal.append(0, [1], [2], [1.0], [0])   # rejected-but-on-disk
    wal.append(0, [7], [8], [1.0], [0])   # the acknowledged retry
    batches = wal.replay()
    assert len(batches) == 1
    np.testing.assert_array_equal(batches[0].rows, [7])
    wal.close()


def test_wal_positional_drop_kills_rejected_record_only(tmp_path):
    """Review finding (round 16): a record that reached disk before
    its fsync raised is tombstoned by the rollback path — the
    tombstone must kill the WHOLE rejected record (even seqs no retry
    re-claims) while leaving the later retry untouched (positional
    semantics)."""
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    # rejected append: 3 ops at seqs 0-2, landed then rolled back
    wal.append(0, [1, 2, 3], [4, 5, 6], [1.0] * 3, [0, 0, 0])
    wal.append_drop(0, 2)
    # the retry re-claims only seq 0 (a smaller batch)
    wal.append(0, [9], [9], [1.0], [0])
    batches = wal.replay()
    assert len(batches) == 1
    np.testing.assert_array_equal(batches[0].rows, [9])  # seqs 1-2
    # of the rejected record stay dead: nothing resurrects
    wal.close()


def test_wal_drop_tombstone_suppresses_replay(tmp_path):
    """A merge-failed range (futures failed honestly on the live
    engine) must not resurrect at recovery."""
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    wal.append(0, [1, 2], [2, 1], [1.0, 1.0], [0, 0])
    wal.append(2, [3], [4], [1.0], [0])
    wal.append_drop(0, 1)
    batches = wal.replay()
    assert [(b.first_seq, b.last_seq) for b in batches] == [(2, 2)]
    wal.close()


# --- snapshot atomicity / corruption fallback --------------------------------


def test_snapshot_atomic_and_corrupt_refused(grid, tmp_path):
    """ISSUE 14 satellite: ``save_version`` writes tmp + os.replace
    (no partial file under the real name), and a corrupt/truncated
    snapshot is REFUSED with a diagnostic naming the file —
    ``load_latest_version`` falls back to the previous retained one."""
    rows, cols = _coo(1)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                               keep_coo=True)
    p1 = str(tmp_path / checkpoint.snapshot_name(0))
    checkpoint.save_version(p1, eng.version)
    assert not os.path.exists(p1 + ".tmp")
    # newer snapshot, then corrupt it (truncate to half)
    p2 = str(tmp_path / checkpoint.snapshot_name(5))
    checkpoint.save_version(p2, eng.version)
    blob = open(p2, "rb").read()
    with open(p2, "wb") as f:
        f.write(blob[: len(blob) // 2])
    with pytest.raises(ValueError, match="ckpt-000000000006"):
        checkpoint.load_version(p2, grid)
    with pytest.warns(UserWarning, match="falling back"):
        v, path = checkpoint.load_latest_version(str(tmp_path), grid)
    assert path == p1  # the previous retained snapshot
    _assert_bit_exact(v, eng.version)
    # nothing loadable at all -> RecoveryError naming the dir
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(RecoveryError, match="no loadable"):
        checkpoint.load_latest_version(str(empty), grid)


def test_checkpoint_retention_prunes(grid, tmp_path):
    """checkpoint_retain bounds the snapshot set; pruning keeps the
    newest (the recovery source) plus the fallback depth."""
    rows, cols = _coo(2)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                               keep_coo=True)
    cfg = ServeConfig(lane_widths=(1,), update_autostart=False,
                      wal_dir=str(tmp_path), checkpoint_retain=2,
                      update_flush=1)
    srv = Server(eng, cfg)
    pairs = _absent_pairs(rows, cols, 4)
    for a, b in pairs:
        srv.submit_update([("insert", a, b), ("insert", b, a)])
        srv.pump_updates(force=True)
        srv.checkpoint_now()
    snaps = checkpoint.list_snapshots(str(tmp_path))
    assert len(snaps) == 2  # bootstrap + 4 manual, pruned to retain=2
    # and the newest one recovers the full state
    wal = open_wal(str(tmp_path))
    v = recover_version(str(tmp_path), wal, grid, kinds=("bfs",))
    wal.close()
    _assert_bit_exact(v, srv.engine.version)
    srv.close()


# --- the crash-recovery property ---------------------------------------------


def _crash_recover_scenario(grid, tmp_path, tag, n_appends, n_merges,
                            ckpt_after, torn):
    """Build a durable server, acknowledge ``n_appends`` write
    batches, merge the first ``n_merges``, checkpoint after
    ``ckpt_after`` merges (None = bootstrap snapshot only), optionally
    tear the final WAL line mid-write — then "crash" (walk away
    without close()) and recover from the files alone.

    The recovered version must be bit-exact with a NEVER-CRASHED
    reference that merged every acknowledged batch, minus a torn tail
    (a torn line was never acknowledged: its append raised before the
    future existed — losing it loses nothing promised)."""
    d = tmp_path / f"crash-{tag}"
    rows, cols = _coo(7)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                               keep_coo=True)
    cfg = ServeConfig(lane_widths=(1,), update_autostart=False,
                      wal_dir=str(d), update_flush=1)
    srv = Server(eng, cfg)
    pairs = _absent_pairs(rows, cols, n_appends)
    batches = [
        [("insert", a, b), ("insert", b, a)] for a, b in pairs
    ]
    for k, ops in enumerate(batches):
        srv.submit_update(ops)
        if k < n_merges:
            srv.pump_updates(force=True)
        if ckpt_after is not None and k + 1 == ckpt_after:
            assert srv.checkpoint_now() is not None
    if torn:
        # one more acknowledged batch... whose append is torn mid-line
        # (the dying-process artifact): simulate by appending a
        # partial record BEHIND the server's back
        with open(str(d / "wal.jsonl"), "a") as f:
            f.write('{"v": "combblas_tpu.wal/v1", "first_se')
    # CRASH: no close(), no drain — the files are all that survives
    wal = open_wal(str(d))
    recovered = recover_version(str(d), wal, grid, kinds=("bfs",))
    wal.close()
    # the never-crashed reference: every acknowledged batch applied
    ref = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                               keep_coo=True).version
    for k, ops in enumerate(batches):
        ref = apply_delta(
            ref, DeltaBatch.from_ops(ops, start_seq=2 * k),
            kinds=("bfs",),
        )
    _assert_bit_exact(recovered, ref)
    # cleanliness: quarantine-free teardown for the abandoned server
    srv.scheduler.close()


def test_crash_recovery_bit_exact_at_every_boundary(grid, tmp_path):
    """THE acceptance property: crashes at every append/merge/
    checkpoint boundary recover bit-exact, zero acknowledged writes
    lost.  Sweeps (appends, merges, checkpoint position) over the
    small-graph 1x1 grid; the torn-final-line artifact rides the
    deepest scenario."""
    cases = []
    for k in (1, 2, 4):
        for m in sorted({0, k // 2, k}):
            for c in sorted({None, m if m else None},
                            key=lambda x: -1 if x is None else x):
                cases.append((k, m, c, False))
    cases.append((4, 2, 2, True))  # torn tail on a mid-merge crash
    cases.append((3, 3, None, True))  # torn tail, bootstrap-only ckpt
    for i, (k, m, c, torn) in enumerate(cases):
        _crash_recover_scenario(
            grid, tmp_path, f"{i}", k, m, c, torn
        )


def test_crash_recovery_distributed_representative(tmp_path):
    """One 2x4-grid representative of the boundary sweep (the tier-1
    mesh): snapshot of an INCREMENTALLY merged version + suffix
    replay, crash after the checkpoint."""
    _crash_recover_scenario(
        Grid.make(2, 4), tmp_path, "dist", 3, 2, 2, False
    )


def test_recovered_server_resumes_lineage(grid, tmp_path):
    """Server.from_recovery boots bit-exact AND keeps writing on the
    same seqno lineage: post-recovery writes merge incrementally and a
    second recovery sees them too (no seq collision, no replay dup)."""
    d = str(tmp_path / "resume")
    rows, cols = _coo(9)
    # headroom reserves re-bucket slots, so the post-recovery insert
    # provably exercises the INCREMENTAL path on the restored sticky
    # layout (without it, bucket_full may legitimately spill — on a
    # live engine exactly as on a recovered one)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                               keep_coo=True, headroom=0.5)
    cfg = ServeConfig(lane_widths=(1,), update_autostart=False,
                      wal_dir=d, update_flush=1)
    srv = Server(eng, cfg)
    pairs = _absent_pairs(rows, cols, 3)
    (a0, b0), (a1, b1), (a2, b2) = pairs
    srv.submit_update([("insert", a0, b0), ("insert", b0, a0)])
    srv.pump_updates(force=True)
    srv.submit_update([("insert", a1, b1), ("insert", b1, a1)])
    # crash with one un-merged acknowledged write
    srv2 = Server.from_recovery(grid, cfg, kinds=("bfs",))
    lev = None
    for (x, y) in ((a0, b0), (a1, b1)):
        lev = srv2.submit("bfs", x)
        srv2.pump(force=True)
        assert lev.result(timeout=60)["levels"][y] == 1
    f = srv2.submit_update([("insert", a2, b2), ("insert", b2, a2)])
    srv2.pump_updates(force=True)
    res = f.result(timeout=60)
    assert res["mode"] == "incremental"  # restored sticky layout holds
    # a third life sees ALL three writes
    srv3 = Server.from_recovery(grid, cfg, kinds=("bfs",))
    _assert_bit_exact(srv3.engine.version, srv2.engine.version)
    for s in (srv, srv2, srv3):
        s.scheduler.close()


def test_boot_from_coo_refuses_unreplayed_wal(grid, tmp_path):
    """Review finding (round 16): booting a FRESH engine from COO over
    a durability dir whose WAL still holds acknowledged writes no
    snapshot covers must REFUSE — the bootstrap snapshot would
    otherwise truncate (destroy) them silently.  Recovery consumes
    the suffix; after it (or a clean close) the same boot succeeds."""
    d = str(tmp_path / "refuse")
    rows, cols = _coo(13)
    cfg = ServeConfig(lane_widths=(1,), update_autostart=False,
                      wal_dir=d, update_flush=64,
                      update_max_delay_s=30.0)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                               keep_coo=True)
    srv = Server(eng, cfg)
    (a, b), = _absent_pairs(rows, cols, 1)
    srv.submit_update([("insert", a, b)])  # acknowledged, un-merged
    # "crash"; a naive re-boot from COO must not destroy the write
    with pytest.raises(RuntimeError, match="would silently destroy"):
        Server(
            GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                                 keep_coo=True),
            cfg,
        )
    # recovery consumes the suffix -> the write survives, and a later
    # boot-from-COO (fresh lineage over the exhausted log) is allowed
    srv2 = Server.from_recovery(grid, cfg, kinds=("bfs",))
    r, c, _v = srv2.engine.version.E.to_host_coo()
    assert (a, b) in set(zip(r.tolist(), c.tolist()))
    srv2.scheduler.close()
    srv3 = Server(
        GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                             keep_coo=True),
        cfg,
    )
    srv3.scheduler.close()
    srv.scheduler.close()


def test_nondurable_home_death_rebuilds_fresh_lineage(tmp_path):
    """Review finding (round 16): without a WAL a dead home cannot be
    promoted — but the supervisor must still REBUILD the slot (the
    engine object outlives its worker; its retained COO is the fresh
    lineage) instead of leaving writes down forever."""
    fr, rows, cols = _mk_fleet(tmp_path, 31, wal=False)
    try:
        fr.warmup(widths=(1, 2))
        _kill_worker(fr, 0)  # the (non-durable) home dies
        out = fr.supervise_once()
        assert out["promoted"] is None and 0 in out["replaced"]
        assert fr.home == 0  # same slot, fresh lineage
        # reads AND writes serve again
        (a, b), = _absent_pairs(rows, cols, 1)
        res = fr.submit_update(
            [("insert", a, b), ("insert", b, a)]
        ).result(timeout=60)
        assert res["fanned_out"] == 1
        for srv in fr.replicas:
            assert srv.submit("bfs", a).result(
                timeout=60
            )["levels"][b] == 1
    finally:
        fr.close(drain=False)


def test_wal_append_failure_rejects_write(grid, tmp_path):
    """A write whose WAL append failed is REJECTED, not acknowledged
    undurable: the buffer rolls back, nothing merges, and the next
    write proceeds on clean sequence numbers."""
    rows, cols = _coo(11)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                               keep_coo=True)
    cfg = ServeConfig(lane_widths=(1,), update_autostart=False,
                      wal_dir=str(tmp_path / "wf"), update_flush=1)
    srv = Server(eng, cfg)
    (a, b), (a2, b2) = _absent_pairs(rows, cols, 2)
    srv.faults.script("wal.append", at=(0,))
    with pytest.raises(RuntimeError, match="NOT acknowledged"):
        srv.submit_update([("insert", a, b)])
    assert srv._upd_buffer.depth() == 0  # rolled back
    assert srv.pump_updates(force=True) == 0  # nothing to merge
    f = srv.submit_update([("insert", a2, b2), ("insert", b2, a2)])
    srv.pump_updates(force=True)
    assert f.result(timeout=60)["ops"] == 2
    # recovery agrees: only the acknowledged write exists
    wal = open_wal(str(tmp_path / "wf"))
    v = recover_version(str(tmp_path / "wf"), wal, grid,
                        kinds=("bfs",))
    wal.close()
    _assert_bit_exact(v, srv.engine.version)
    srv.scheduler.close()


# --- fleet: routing, supervision, promotion, drain ---------------------------


def _mk_fleet(tmp_path, seed, replicas=2, wal=True, grid_shape=(1, 1),
              **cfg_kw):
    """Most fleet-healing mechanics are grid-independent (threads,
    queues, files): they run on the cheap 1x1 grid; the promotion and
    routing tests keep a 2x4 tier-1-mesh representative."""
    rows, cols = _coo(seed)
    kw = dict(lane_widths=(1, 2), update_flush=1,
              update_max_delay_s=0.005)
    kw.update(cfg_kw)
    cfg = ServeConfig(**kw)
    fr = FleetRouter.build(
        Grid.make(*grid_shape), rows, cols, N, replicas=replicas,
        config=cfg, kinds=("bfs",),
        wal_dir=str(tmp_path / "fleet-wal") if wal else None,
    )
    return fr, rows, cols


def _kill_worker(fr, i, timeout=5.0):
    """Deterministically kill replica i's worker thread through the
    replica.death fault point (woken by a direct submit)."""
    fr.replicas[i].faults.script("replica.death", at=(0,))
    probe = fr.replicas[i].submit("bfs", 1)  # wakes THAT worker
    t0 = time.monotonic()
    while not fr._dead(i):
        assert time.monotonic() - t0 < timeout, "worker did not die"
        time.sleep(0.005)
    return probe


def test_route_order_skips_dead_replica(tmp_path):
    """ISSUE 14 satellite: a dead replica's EMPTY queue must not
    attract traffic — routing skips down/closed replicas."""
    fr, rows, cols = _mk_fleet(tmp_path, 21, wal=False,
                               grid_shape=(2, 4))
    try:
        fr.warmup(widths=(1, 2))
        _kill_worker(fr, 1)  # the non-home replica dies
        # the dead replica has queue depth <= 1 (the probe), yet every
        # routed submit lands on the live one
        assert fr._route_order() == [0]
        for _ in range(4):
            assert fr.submit("bfs", 2).result(timeout=60) is not None
        assert fr.submitted[1] == 0
        # replacement (no WAL: rebuilt from the home's retained COO)
        # rejoins the rotation
        assert fr.supervise_once()["replaced"] == [1]
        assert set(fr._route_order()) == {0, 1}
    finally:
        fr.close(drain=False)


def test_fanout_failure_lags_visibly_and_heals(tmp_path):
    """ISSUE 14 satellite: a replica whose rebuild fails mid-fan-out
    LAGS (stats/health degrade) instead of failing the write, and the
    next fan-out retries and heals it."""
    fr, rows, cols = _mk_fleet(tmp_path, 22, wal=False)
    try:
        fr.warmup(widths=(1, 2))
        pairs = _absent_pairs(rows, cols, 2)
        fr.faults.script("fleet.fanout", at=(0,))  # first fan-out dies
        (a, b), (a2, b2) = pairs
        res = fr.submit_update(
            [("insert", a, b), ("insert", b, a)]
        ).result(timeout=60)
        assert res["fanned_out"] == 0 and res["lagging"] == [1]
        assert fr.health()["status"] == "degraded"
        assert fr.lagging() == [1]
        # replica 1 still serves the OLD version, honestly
        assert fr.replicas[1].submit("bfs", a).result(
            timeout=60
        )["levels"][b] != 1
        # next fan-out (the second write) retries replica 1 -> heals
        res = fr.submit_update(
            [("insert", a2, b2), ("insert", b2, a2)]
        ).result(timeout=60)
        assert res["fanned_out"] == 1 and res["lagging"] == []
        assert fr.health()["status"] == "ok"
        lev = fr.replicas[1].submit("bfs", a).result(timeout=60)
        assert lev["levels"][b] == 1  # the lagged write arrived too
    finally:
        fr.close(drain=False)


@pytest.mark.slow
def test_supervisor_replaces_dead_replica_bit_exact(tmp_path):
    """A dead (non-home) replica is quarantined (pending futures fail
    honestly), rebuilt from checkpoint+WAL and re-admitted serving the
    acknowledged writes, warm.

    ``slow``: the tier-1 representative of the supervise->quarantine->
    rebuild path is ``test_home_death_promotes_at_wal_frontier``
    (which also replaces the dead ex-home through the same code)."""
    fr, rows, cols = _mk_fleet(tmp_path, 23, wal=True)
    try:
        fr.warmup(widths=(1, 2))
        (a, b), = _absent_pairs(rows, cols, 1)
        fr.submit_update(
            [("insert", a, b), ("insert", b, a)]
        ).result(timeout=60)
        probe = _kill_worker(fr, 1)
        out = fr.supervise_once()
        assert out["detected"] == [1] and out["replaced"] == [1]
        assert isinstance(probe.exception(timeout=10),
                          ReplicaDeadError)  # honest, never stranded
        # the replacement serves the acknowledged write, bit-exact
        # with the home
        _assert_bit_exact(fr.replicas[1].engine.version,
                          fr.replicas[0].engine.version)
        mark = fr.replicas[1].engine.trace_mark()
        lev = fr.replicas[1].submit("bfs", a).result(timeout=60)
        assert lev["levels"][b] == 1
        assert fr.replicas[1].engine.retraces_since(mark) == 0
        assert fr.replacements == 1
        assert fr.health()["status"] == "ok"
    finally:
        fr.close(drain=False)


def test_home_death_promotes_at_wal_frontier(tmp_path):
    """THE failover: the home dies with an acknowledged-but-unmerged
    write buffered.  Promotion recovers the new home at the WAL's
    seqno frontier (the buffered write INCLUDED — acknowledged means
    durable), fails the dead home's buffered futures honestly, and
    the write lane continues on the single preserved lineage."""
    fr, rows, cols = _mk_fleet(
        tmp_path, 24, replicas=3, wal=True, grid_shape=(2, 4),
        # writes BUFFER (no flush): the promotion must not depend on
        # the dead home having merged
        update_flush=64, update_max_delay_s=30.0,
    )
    try:
        fr.warmup(widths=(1, 2))
        (a, b), (a2, b2) = _absent_pairs(rows, cols, 2)
        buffered = fr.submit_update([("insert", a, b),
                                     ("insert", b, a)])
        assert not buffered.done()
        _kill_worker(fr, 0)
        out = fr.supervise_once()
        assert out["promoted"] is not None and fr.home == out["promoted"]
        assert fr.promotions == 1
        # honest failure of the buffered future...
        assert isinstance(buffered.exception(timeout=10),
                          ReplicaDeadError)
        # ...but ZERO acknowledged-write loss: the new home serves it
        lev = fr.replicas[fr.home].submit("bfs", a).result(timeout=60)
        assert lev["levels"][b] == 1
        # the lineage continues: a post-promotion write lands
        # everywhere (old home's slot was replaced too).  The config
        # buffers writes for 30 s by design (the buffered-future
        # scenario above), so force the merge deterministically.
        f2 = fr.submit_update(
            [("insert", a2, b2), ("insert", b2, a2)]
        )
        fr.replicas[fr.home].pump_updates(force=True)
        res = f2.result(timeout=60)
        assert res["fanned_out"] == len(fr.replicas) - 1
        for srv in fr.replicas:
            assert srv.submit("bfs", a2).result(
                timeout=60
            )["levels"][b2] == 1
        assert fr.health()["status"] == "ok"
    finally:
        fr.close(drain=False)


def test_read_retry_on_next_best_replica(tmp_path):
    """Bounded read retry (reads only): with one replica failing every
    execution, router-submitted reads still succeed via the retry on
    the other replica."""
    fr, rows, cols = _mk_fleet(tmp_path, 25, wal=False)
    try:
        fr.warmup(widths=(1, 2))
        fr.replicas[0].faults.rate("engine.execute", 1.0, seed=1)
        for _ in range(6):
            assert fr.submit("bfs", 3).result(timeout=60) is not None
        assert fr.read_retries >= 1
        # malformed roots are NOT retried: one honest ValueError
        bad = fr.submit("bfs", N + 99)
        assert isinstance(bad.exception(timeout=60), ValueError)
    finally:
        fr.close(drain=False)


def test_fleet_close_drain_flushes_vs_aborts(tmp_path):
    """ISSUE 14 satellite, the PR 9 single-server guarantee at fleet
    scope: close(drain=True) flushes the home's buffered writes
    through merge (durable: WAL + final checkpoint) before returning;
    close(drain=False) aborts the buffered futures."""
    # drain=True: the buffered write lands and survives into recovery
    fr, rows, cols = _mk_fleet(
        tmp_path, 26, wal=True,
        update_flush=64, update_max_delay_s=30.0,
    )
    (a, b), = _absent_pairs(rows, cols, 1)
    f = fr.submit_update([("insert", a, b), ("insert", b, a)])
    fr.close(drain=True)
    assert f.result(timeout=10)["ops"] == 2
    wal_dir = fr.wal_dir
    g = Grid.make(1, 1)
    wal = open_wal(wal_dir)
    v = recover_version(wal_dir, wal, g, kinds=("bfs",))
    wal.close()
    _assert_bit_exact(v, fr.replicas[0].engine.version)
    # drain=False: buffered futures abort (and stay aborted)
    fr2, rows2, cols2 = _mk_fleet(
        tmp_path / "nf", 27, wal=False,
        update_flush=64, update_max_delay_s=30.0,
    )
    (a2, b2), = _absent_pairs(rows2, cols2, 1)
    f2 = fr2.submit_update([("insert", a2, b2), ("insert", b2, a2)])
    fr2.close(drain=False)
    assert isinstance(f2.exception(timeout=10), RuntimeError)


def test_drain_restore_rolling_restart(tmp_path):
    """Upgrades are first-class: drain/restore cycles every replica
    with reads surviving throughout, a mid-drain write healing via
    the restore fan-out, and ZERO retraces (the engines are reused
    warm)."""
    fr, rows, cols = _mk_fleet(tmp_path, 28, wal=True)
    try:
        fr.warmup(widths=(1, 2))
        (a, b), = _absent_pairs(rows, cols, 1)
        marks = [s.engine.trace_mark() for s in fr.replicas]
        f = fr.submit_update([("insert", a, b), ("insert", b, a)])
        assert fr.rolling_restart() == 2
        f.result(timeout=60)
        assert fr.lagging() == []
        for srv, mark in zip(fr.replicas, marks):
            assert srv.submit("bfs", a).result(
                timeout=60
            )["levels"][b] == 1
            assert srv.engine.retraces_since(mark) == 0
        st = fr.stats()
        assert st["draining"] == [] and st["promotions"] == 0
    finally:
        fr.close(drain=False)


@pytest.mark.slow
def test_fleet_from_recovery_boots_whole_fleet(tmp_path):
    """FleetRouter.from_recovery: every replica = snapshot + WAL
    replay, home re-attached at the frontier, writes resume.

    ``slow``: the tier-1 representative of the recovery-boot path is
    ``test_recovered_server_resumes_lineage`` (Server.from_recovery —
    the same recover+attach machinery, one replica)."""
    fr, rows, cols = _mk_fleet(tmp_path, 29, wal=True)
    (a, b), (a2, b2) = _absent_pairs(rows, cols, 2)
    fr.submit_update([("insert", a, b),
                      ("insert", b, a)]).result(timeout=60)
    fr.close(drain=True)
    cfg = ServeConfig(lane_widths=(1, 2), update_flush=1,
                      update_max_delay_s=0.005)
    with FleetRouter.from_recovery(
        Grid.make(1, 1), replicas=2, config=cfg, kinds=("bfs",),
        wal_dir=str(tmp_path / "fleet-wal"),
    ) as fr2:
        fr2.warmup(widths=(1, 2))
        for srv in fr2.replicas:
            assert srv.submit("bfs", a).result(
                timeout=60
            )["levels"][b] == 1
        res = fr2.submit_update(
            [("insert", a2, b2), ("insert", b2, a2)]
        ).result(timeout=60)
        assert res["fanned_out"] == 1


# --- threaded kill-storm soak (slow) ------------------------------------------


@pytest.mark.slow
@pytest.mark.chaos
def test_kill_storm_soak(tmp_path):
    """Mixed read/write load with replica kills (home included) while
    the supervisor heals: availability holds, every acknowledged
    write survives into the final recovered state."""
    import threading

    fr, rows, cols = _mk_fleet(tmp_path, 30, replicas=3, wal=True,
                               grid_shape=(2, 4))
    acked = []
    try:
        fr.warmup(widths=(1, 2))
        fr.start_supervisor(interval_s=0.02)
        pairs = _absent_pairs(rows, cols, 12)
        stop = threading.Event()

        def writer():
            for a, b in pairs:
                try:
                    f = fr.submit_update(
                        [("insert", a, b), ("insert", b, a)]
                    )
                    f.result(timeout=60)
                    acked.append((a, b))
                except Exception:
                    pass  # failed writes may or may not be durable
                time.sleep(0.01)

        wt = threading.Thread(target=writer)
        wt.start()
        ok = bad = 0
        for i in range(120):
            if i in (30, 70):  # kill a replica / the home mid-stream
                victim = fr.home if i == 70 else (fr.home + 1) % 3
                try:
                    _kill_worker(fr, victim)
                except AssertionError:
                    pass
            try:
                fr.submit("bfs", int(rows[i % len(rows)])).result(
                    timeout=60
                )
                ok += 1
            except Exception:
                bad += 1
        wt.join(120)
        stop.set()
        assert ok / (ok + bad) >= 0.95
        # let the supervisor settle any last kill before closing (a
        # quarantined slot stays in _needs_rebuild until re-admitted)
        deadline = time.monotonic() + 10
        while (
            fr._needs_rebuild
            or any(fr._dead(i) for i in range(3))
        ) and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        fr.close(drain=True)
    # zero acknowledged-write loss: recover from the files and check
    # every acked edge exists
    wal = open_wal(str(tmp_path / "fleet-wal"))
    v = recover_version(str(tmp_path / "fleet-wal"), wal,
                        Grid.make(2, 4), kinds=("bfs",))
    wal.close()
    r, c, _vals = v.E.to_host_coo()
    have = set(zip(r.tolist(), c.tolist()))
    missing = [p for p in acked if p not in have]
    assert not missing, f"acknowledged writes lost: {missing}"
