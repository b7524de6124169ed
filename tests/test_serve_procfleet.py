"""Process-isolated serving fleet (round 17, ISSUE 15): subprocess
replicas with real crash domains behind the shared routing/supervision
policy — IPC framing, per-request deadlines, heartbeat liveness,
SIGKILL/SIGSTOP chaos over the WAL/checkpoint substrate.

Tier-1 keeps ONE spawning representative (single replica, 1x1 grid,
pre-staged checkpoint, deterministic ``supervise_once``) plus
spawn-free unit tests of the IPC channel, the parent-side replica
client (stub responder over a socketpair — no subprocess, no jax
child), and the deterministic process fault plan.  The real-signal
chaos scenarios (SIGKILL respawn, SIGSTOP heartbeat-timeout
promotion) are ``slow``.
"""

import json
import os
import signal
import socket
import threading
import time

import numpy as np
import pytest

from combblas_tpu.dynamic import open_wal, recover_version
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import (
    BackpressureError,
    GraphEngine,
    ProcessFaultPlan,
    ProcessFleet,
    ServeConfig,
)
from combblas_tpu.serve.frame import Channel, ChannelClosed
from combblas_tpu.serve.procfleet import (
    IpcTimeoutError,
    ReplicaDeadError,
    ReplicaProc,
)
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


# --- IPC framing (no processes) ----------------------------------------------


def test_ipc_channel_roundtrip_with_ndarrays():
    a, b = socket.socketpair()
    ca, cb = Channel(a), Channel(b)
    msg = {
        "id": 1, "ok": True,
        "result": {"levels": np.arange(6, dtype=np.int32).reshape(2, 3),
                   "n": np.int64(7), "f": np.float32(0.5)},
    }
    ca.send(msg)
    got = cb.recv(timeout=5)  # arrays rebuilt by decode()
    np.testing.assert_array_equal(
        got["result"]["levels"], np.arange(6).reshape(2, 3)
    )
    assert got["result"]["levels"].dtype == np.int32
    assert got["result"]["n"] == 7
    # a closed peer is a clean ChannelClosed, never a desync
    ca.close()
    with pytest.raises(ChannelClosed):
        cb.recv(timeout=5)
    cb.close()


def test_ipc_sparse_frontier_and_bf16_roundtrip():
    """ISSUE 19: the ``__spf__`` typed envelope round-trips a
    SparseFrontier (dtypes pinned: rows int32, lanes uint8, optional
    vals f32) through the length-prefixed frame codec, the width
    bound is enforced at construction, and the bf16 pack/unpack pair
    is round-to-nearest-even with |err| <= 2^-8 relative."""
    from combblas_tpu.serve.frame import (
        SparseFrontier, pack_bf16, unpack_bf16,
    )

    a, b = socket.socketpair()
    ca, cb = Channel(a), Channel(b)
    sf = SparseFrontier(40, 3, np.array([1, 7, 39]),
                        np.array([0, 2, 1]))
    sfv = SparseFrontier(40, 3, np.array([5]), np.array([1]),
                         np.array([0.25]))
    ca.send({"id": 1, "ok": True, "result": {"xs": sf, "ds": sfv}})
    got = cb.recv(timeout=5)["result"]
    for orig, back in ((sf, got["xs"]), (sfv, got["ds"])):
        assert isinstance(back, SparseFrontier)
        assert (back.n, back.width, back.nnz) == (orig.n, orig.width,
                                                  orig.nnz)
        np.testing.assert_array_equal(back.rows, orig.rows)
        assert back.rows.dtype == np.int32
        np.testing.assert_array_equal(back.lanes, orig.lanes)
        assert back.lanes.dtype == np.uint8
    assert got["xs"].vals is None
    np.testing.assert_array_equal(got["ds"].vals, [0.25])
    assert got["ds"].vals.dtype == np.float32
    # to_dense scatters (row, lane) -> value (row id when vals=None)
    dense = got["xs"].to_dense(np.int32(-1))
    assert dense.shape == (40, 3)
    assert dense[7, 2] == 7 and dense[0, 0] == -1
    assert got["xs"].nbytes() == 3 * (4 + 1)
    ca.close()
    cb.close()
    with pytest.raises(ValueError, match="width"):
        SparseFrontier(10, 257, np.zeros(0), np.zeros(0))
    # bf16: round-to-nearest-even, exact on bf16-representable values
    q = np.array([0.0, 1.0, -2.5, 3.140625, 1e-3, 7e4], np.float32)
    back = unpack_bf16(pack_bf16(q))
    np.testing.assert_array_equal(back[:4], q[:4])  # representable
    assert np.all(np.abs(back - q) <= np.abs(q) * 2.0 ** -8)


def test_ipc_send_survives_reader_poll_timeout():
    """ISSUE 19 (send-stall fix): ``settimeout`` is socket-GLOBAL, so
    a reader thread polling ``recv`` with a short tick must not
    impose that tick on a concurrent send of a frame bigger than the
    kernel socket buffer headed to a peer that is slow to drain (the
    scale-12 boot payload scenario).  The chunked sender keeps
    partial progress across ticks instead of dying with a spurious
    'peer gone: timed out'."""
    a, b = socket.socketpair()
    ca, cb = Channel(a), Channel(b)
    stop = threading.Event()

    def _reader_ticks():
        # the procfleet reader-loop shape: recv with a tiny poll tick,
        # constantly resetting the socket timeout under the sender
        while not stop.is_set():
            try:
                ca.recv(timeout=0.02)
            except socket.timeout:
                continue
            except ChannelClosed:
                return

    t = threading.Thread(target=_reader_ticks, daemon=True)
    t.start()
    big = {"id": 1, "blob": np.arange(1 << 20, dtype=np.int64)}  # 8 MB
    got: dict = {}

    def _slow_drain():
        time.sleep(1.0)  # peer busy "importing its runtime"
        got.update(cb.recv(timeout=30))

    d = threading.Thread(target=_slow_drain, daemon=True)
    d.start()
    ca.send(big)  # old sendall: ChannelClosed within one poll tick
    d.join(timeout=30)
    stop.set()
    assert not d.is_alive()
    np.testing.assert_array_equal(got["blob"], big["blob"])
    ca.close()
    cb.close()
    t.join(timeout=5)


def test_ipc_oversized_frame_refused():
    from combblas_tpu.serve import frame

    a, b = socket.socketpair()
    ca = Channel(a)
    big = "x" * (frame.MAX_FRAME + 1)
    with pytest.raises(ValueError, match="too large"):
        ca.send({"blob": big})
    ca.close()
    b.close()


# --- parent-side replica client over a stub responder ------------------------


def _stub_replica(script=None, idx=0, **kw):
    """A ReplicaProc whose 'child' is an in-process responder thread —
    the parent-side bookkeeping (deadline sweep, heartbeat tracking,
    error mapping, quarantine) without spawning an interpreter."""
    a, b = socket.socketpair()
    stop = threading.Event()
    ch_child = Channel(b)

    def responder():
        while not stop.is_set():
            try:
                m = ch_child.recv(timeout=0.05)
            except socket.timeout:
                continue
            except ChannelClosed:
                return
            op = m.get("op")
            if op == "ping":
                ch_child.send({"id": m["id"], "ok": True,
                               "result": {"pong": True}})
            elif op == "hang":
                pass  # never answers: the deadline sweep's case
            elif op == "badroot":
                ch_child.send({"id": m["id"], "ok": False,
                               "etype": "ValueError",
                               "error": "root out of range"})
            elif op == "busy":
                ch_child.send({"id": m["id"], "ok": False,
                               "etype": "BackpressureError",
                               "error": "queue full",
                               "retry_after_s": 0.02})
            elif op == "hb":
                ch_child.send({"hb": {"depth": 3, "serving": True,
                                      "t": time.time()}})

    t = threading.Thread(target=responder, daemon=True)
    t.start()
    rp = ReplicaProc(idx, None, Channel(a), **kw)
    return rp, stop, ch_child


def test_replica_client_rpc_deadline_and_error_mapping():
    rp, stop, _ch = _stub_replica(ipc_timeout_s=30.0)
    try:
        assert rp.call("ping")["pong"] is True
        # per-request deadline: a hung op fails ITS future with the
        # replica-level (read-retried) error — the router never wedges
        f = rp.rpc("hang", timeout_s=0.2)
        with pytest.raises(IpcTimeoutError):
            f.result(timeout=10)
        assert rp.ipc_timeouts == 1
        # child-side taxonomy survives the wire
        with pytest.raises(ValueError):
            rp.rpc("badroot", timeout_s=5).result(timeout=10)
        exc = rp.rpc("busy", timeout_s=5).exception(timeout=10)
        assert isinstance(exc, BackpressureError)
        # heartbeats update the hang detector's clock
        rp.rpc("hb", timeout_s=5)
        t0 = time.monotonic()
        while rp.last_hb.get("depth") != 3:
            assert time.monotonic() - t0 < 5
            time.sleep(0.005)
        assert rp.heartbeat_age() < 5
        assert rp.depth() >= 3  # hb depth counts toward routing load
    finally:
        stop.set()
        rp.quarantine(ReplicaDeadError("teardown"))


def test_replica_client_quarantine_fails_pending_honestly():
    rp, stop, _ch = _stub_replica()
    try:
        f = rp.rpc("hang", timeout_s=60)
        n = rp.quarantine(ReplicaDeadError("replica 0 died"))
        assert n == 1
        assert isinstance(f.exception(timeout=5), ReplicaDeadError)
        assert not rp.is_serving()
        with pytest.raises(ReplicaDeadError):
            rp.rpc("ping")
    finally:
        stop.set()


def test_replica_client_local_backpressure_bound():
    rp, stop, _ch = _stub_replica(max_inflight=2)
    try:
        rp.rpc("hang", timeout_s=60)
        rp.rpc("hang", timeout_s=60)
        with pytest.raises(BackpressureError):
            rp.submit("bfs", 1)
    finally:
        stop.set()
        rp.quarantine(ReplicaDeadError("teardown"))


def test_broken_channel_fails_pending_and_marks_dead():
    rp, stop, ch_child = _stub_replica()
    try:
        f = rp.rpc("hang", timeout_s=60)
        ch_child.close()  # the process died: EOF on the socket
        assert isinstance(f.exception(timeout=10), ReplicaDeadError)
        t0 = time.monotonic()
        while not rp.broken:
            assert time.monotonic() - t0 < 5
            time.sleep(0.005)
        assert not rp.is_serving()
    finally:
        stop.set()


# --- deterministic process fault plan ----------------------------------------


def test_process_fault_plan_is_deterministic():
    plan = ProcessFaultPlan()
    plan.sigkill(2, replica="home").sigstop(4, replica=1)
    fired = []
    for _ in range(6):
        fired.extend(plan.step())
    assert fired == [("SIGKILL", "home"), ("SIGSTOP", 1)]
    assert plan.stats()["calls"] == 6
    assert [f[0] for f in plan.stats()["fired"]] == [2, 4]
    # unarmed plans cost one attribute read and fire nothing
    assert ProcessFaultPlan().step() == []


# --- the tier-1 spawning representative --------------------------------------


def test_single_process_replica_end_to_end(tmp_path):
    """THE fast representative (ISSUE 15 budget satellite): one
    subprocess replica on a 1x1 grid booted from a pre-staged
    checkpoint — reads over IPC, zero post-warmup retraces asserted
    over IPC, a WAL-durable write, heartbeat surfaced in health(),
    deterministic supervise_once, clean close, and crash recovery
    from the files agreeing with the served state."""
    rows, cols = _coo(41)
    grid = Grid.make(1, 1)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                               keep_coo=True, headroom=0.5)
    ckpt = str(tmp_path / "boot.npz")
    checkpoint.save_version(ckpt, eng.version)
    wal_dir = str(tmp_path / "wal")
    fr = ProcessFleet.from_checkpoint(
        ckpt, (1, 1), replicas=1, kinds=("bfs",),
        config=ServeConfig(lane_widths=(1, 2), update_flush=1,
                           update_max_delay_s=0.005),
        wal_dir=wal_dir, workdir=str(tmp_path / "proc"),
        hb_interval_s=0.05, hb_timeout_s=5.0,
    )
    try:
        marks = fr.trace_marks()
        # reads route over IPC and answer exactly like the donor
        lev = fr.submit("bfs", 3).result(timeout=60)["levels"]
        ref = eng.execute("bfs", np.asarray([3], np.int32))["levels"]
        np.testing.assert_array_equal(
            np.asarray(lev), np.asarray(ref)[:, 0]  # lane 0 = root 3
        )
        # zero post-warmup retraces IN THE CHILD, asserted over IPC
        # (the boot warmup claim)
        assert fr.retraces_since(marks) == 0
        # a write is WAL-durable before its future resolves; headroom
        # keeps the merge incremental so plans survive
        (a, b), (a2, b2) = _absent_pairs(rows, cols, 2)
        res = fr.submit_update(
            [("insert", a, b), ("insert", b, a)]
        ).result(timeout=60)
        assert res["ops"] == 2 and res["lagging"] == []
        lev = fr.submit("bfs", a).result(timeout=60)["levels"]
        assert np.asarray(lev)[b] == 1
        # heartbeat liveness is a first-class health fact
        h = fr.health()
        assert h["status"] == "ok" and h["durable"]
        assert h["replicas"][0]["heartbeat_age_s"] < 5.0
        assert h["replicas"][0]["pid"] == fr.replicas[0].proc.pid
        # nothing to heal: the deterministic supervision pass is a
        # no-op on a healthy fleet
        assert fr.supervise_once() == {
            "detected": [], "promoted": None, "replaced": [],
        }
        # close-race regression (round-17 review): a write racing
        # close(drain=True) must SETTLE — merged+durable on the home,
        # fanned or honestly un-fanned — never strand against the
        # shut-down fan executor
        late = fr.submit_update([("insert", a2, b2),
                                 ("insert", b2, a2)])
    finally:
        fr.close(drain=True)
    assert late.result(timeout=60)["ops"] == 2
    # the subprocess exited cleanly and the durable files recover the
    # exact served state (acknowledged write included)
    assert fr.replicas[0].proc.poll() is not None
    wal = open_wal(wal_dir)
    v = recover_version(wal_dir, wal, grid, kinds=("bfs",))
    wal.close()
    rr, rc, _ = v.E.to_host_coo()
    assert (a, b) in set(zip(rr.tolist(), rc.tolist()))


# --- fleet observability plane (round 18, ISSUE 16) --------------------------


def test_fleet_observability_plane_end_to_end(tmp_path):
    """ISSUE 16 acceptance: over a REAL 2-replica subprocess fleet,
    one sampled request yields ONE stitched trace whose router + IPC +
    child stage marks telescope exactly to the trace wall (two
    processes, one clock-skew-safe timeline); heartbeat-piggybacked
    child snapshots federate into one ``/metrics`` scrape with
    ``replica=`` labels; and the supervision timeline records the
    spawns as validated ``fleetlog/v1`` JSONL.  The only spawning
    round-18 test — everything else in the plane is stub-covered
    (test_obs.py / test_obs_serve.py)."""
    import urllib.request

    from combblas_tpu import obs
    from combblas_tpu.obs import export as obs_export
    from combblas_tpu.obs import trace as obs_trace

    rows, cols = _coo(41)
    grid = Grid.make(1, 1)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                               keep_coo=True)
    ckpt = str(tmp_path / "boot.npz")
    checkpoint.save_version(ckpt, eng.version)
    obs.enable(install_hooks=False)
    obs_trace.set_sample_rate(1.0)
    fr = None
    try:
        fr = ProcessFleet.from_checkpoint(
            ckpt, (1, 1), replicas=2, kinds=("bfs",),
            config=ServeConfig(lane_widths=(1, 2)),
            wal_dir=str(tmp_path / "wal"),
            workdir=str(tmp_path / "proc"),
            hb_interval_s=0.05, hb_timeout_s=5.0,
            metrics_interval_s=0.05,
        )
        t0 = time.perf_counter()
        lev = fr.submit("bfs", 3).result(timeout=60)["levels"]
        e2e = time.perf_counter() - t0
        ref = eng.execute("bfs", np.asarray([3], np.int32))["levels"]
        np.testing.assert_array_equal(
            np.asarray(lev), np.asarray(ref)[:, 0]
        )
        # ONE stitched trace: router marks + child marks, one record
        stitched = [r for r in obs_trace.records()
                    if r["labels"].get("fleet") == "process"]
        assert len(stitched) == 1
        (rec,) = stitched
        stages = [s["stage"] for s in rec["stages"]]
        assert stages[:2] == ["route", "ipc_send"]  # router-side
        assert stages[-1] == "ipc_recv"
        for child_stage in ("queue_wait", "assemble", "execute",
                            "scatter"):
            assert child_stage in stages  # shipped back over IPC
        assert "ipc_wait" in stages  # the residual the child can't see
        # the telescoping invariant ACROSS the process boundary: the
        # child contributes durations only, scaled into the router's
        # observed window, so the stages sum to the wall exactly
        assert sum(s["s"] for s in rec["stages"]) == pytest.approx(
            rec["wall_s"], abs=1e-6
        )
        assert rec["wall_s"] <= e2e + 0.05
        assert rec["labels"]["replica"] in (0, 1)
        assert rec["labels"]["kind"] == "bfs"
        # metrics federation: both children piggyback registry
        # snapshots on their heartbeats...
        deadline = time.time() + 10
        while time.time() < deadline and not all(
            rp.last_metrics for rp in fr.replicas
        ):
            time.sleep(0.02)
        assert all(rp.last_metrics for rp in fr.replicas)
        fr.supervise_once()  # tick emits the heartbeat-age gauges
        # ...and ONE scrape serves the whole fleet, replica-labeled
        port = fr.serve_metrics()
        base = f"http://127.0.0.1:{port}"
        text = urllib.request.urlopen(
            f"{base}/metrics", timeout=10
        ).read().decode()
        parsed = obs_export.parse_exposition(text)
        child_reqs = [
            k for k in parsed
            if k[0] == "combblas_serve_requests" and 'replica="' in k[1]
        ]
        assert child_reqs  # child-process counters, federated
        assert any(
            k[0] == "combblas_serve_procfleet_heartbeat_age_s"
            for k in parsed
        )
        hz = json.loads(urllib.request.urlopen(
            f"{base}/healthz", timeout=10
        ).read())
        assert hz["status"] == "ok"
        sz = json.loads(urllib.request.urlopen(
            f"{base}/statz", timeout=10
        ).read())
        assert sz["fleetlog"]["recorded"] >= 2
        # supervision timeline: both spawns recorded, schema-valid
        logged = obs.parse_jsonl(fr.fleetlog.path)
        assert logged[0]["schema"] == obs.FLEETLOG_SCHEMA
        spawns = [r for r in logged if r.get("name") == "fleet.spawn"]
        assert sorted(r["replica"] for r in spawns) == [0, 1]
        assert all(r["pid"] > 0 for r in spawns)
    finally:
        if fr is not None:
            fr.close(drain=True)
        obs_trace.set_sample_rate(None)
        obs_trace.clear()
        obs.disable()
        obs.reset()
    assert fr._scrape is None  # close() stops the scrape thread


# --- real-signal chaos (slow) -------------------------------------------------


@pytest.mark.slow
@pytest.mark.chaos
def test_sigkill_and_sigstop_chaos_heals(tmp_path):
    """Real crash domains: SIGKILL a non-home replica (respawn from
    checkpoint+WAL serves every acknowledged write), then SIGSTOP the
    home — a HANG, not a death: heartbeat timeout detects it, its
    in-flight futures fail honestly instead of wedging the router,
    and promotion at the WAL frontier moves the write lane to a
    survivor.  The tier-1 representative of the spawn/IPC/supervise
    path is ``test_single_process_replica_end_to_end``."""
    rows, cols = _coo(42)
    fr = ProcessFleet.build(
        (1, 1), rows, cols, N, replicas=3, kinds=("bfs",),
        config=ServeConfig(lane_widths=(1, 2), update_flush=1,
                           update_max_delay_s=0.005),
        wal_dir=str(tmp_path / "wal"),
        workdir=str(tmp_path / "proc"),
        hb_interval_s=0.1, hb_timeout_s=1.5,
        from_coo_kw={"headroom": 0.5},
    )
    try:
        pairs = _absent_pairs(rows, cols, 2)
        (a0, b0), (a1, b1) = pairs
        fr.submit_update(
            [("insert", a0, b0), ("insert", b0, a0)]
        ).result(timeout=60)

        # -- SIGKILL a non-home replica: crash detection + respawn
        victim = (fr.home + 1) % 3
        os.kill(fr.replicas[victim].proc.pid, signal.SIGKILL)
        t0 = time.monotonic()
        while not fr._dead(victim):
            assert time.monotonic() - t0 < 10
            time.sleep(0.02)
        out = fr.supervise_once()
        assert victim in out["replaced"]
        lev = fr.replicas[victim].submit(
            "bfs", a0
        ).result(timeout=60)["levels"]
        assert np.asarray(lev)[b0] == 1  # acked write survived SIGKILL

        # -- SIGSTOP the home: hang detection via heartbeat timeout
        home0 = fr.home
        os.kill(fr.replicas[home0].proc.pid, signal.SIGSTOP)
        stuck = fr.replicas[home0].submit("bfs", a0)  # in-flight
        t0 = time.monotonic()
        while not fr._dead(home0):
            assert time.monotonic() - t0 < 15
            time.sleep(0.02)
        out = fr.supervise_once()
        assert out["promoted"] is not None and fr.home != home0
        # honest failure, not a wedge: the stopped replica's future
        assert isinstance(stuck.exception(timeout=30),
                          (ReplicaDeadError, IpcTimeoutError))
        # routed reads keep serving throughout
        for _ in range(4):
            assert fr.submit("bfs", a0).result(timeout=60) is not None
        # the write lane continues on the promoted lineage, fleet-wide
        res = fr.submit_update(
            [("insert", a1, b1), ("insert", b1, a1)]
        ).result(timeout=60)
        assert res["fanned_out"] == 2 and res["lagging"] == []
        for rp in fr.replicas:
            lev = rp.submit("bfs", a1).result(timeout=60)["levels"]
            assert np.asarray(lev)[b1] == 1
        st = fr.stats()
        assert st["promotions"] == 1 and st["replacements"] == 2
        assert fr.health()["status"] == "ok"
    finally:
        fr.close(drain=False)


@pytest.mark.slow
@pytest.mark.chaos
def test_scripted_fault_plan_kills_through_router(tmp_path):
    """``ProcessFaultPlan`` fires real signals at scripted routed-
    submit indices (deterministic chaos, the FaultInjector philosophy
    at the process level) while the supervisor heals in the
    background — availability holds and every routed read settles."""
    rows, cols = _coo(43)
    fr = ProcessFleet.build(
        (1, 1), rows, cols, N, replicas=2, kinds=("bfs",),
        config=ServeConfig(lane_widths=(1, 2)),
        wal_dir=str(tmp_path / "wal"),
        workdir=str(tmp_path / "proc"),
        hb_interval_s=0.1, hb_timeout_s=1.5,
    )
    try:
        fr.start_supervisor(interval_s=0.05)
        fr.proc_faults.sigkill(5, replica=(fr.home + 1) % 2)
        ok = bad = 0
        for i in range(30):
            try:
                fr.submit("bfs", int(rows[i % len(rows)])).result(
                    timeout=60
                )
                ok += 1
            except Exception:
                bad += 1
        assert fr.sigkills == 1
        assert ok / (ok + bad) >= 0.9
        # wait for the supervisor to heal the kill before closing
        deadline = time.monotonic() + 30
        while (
            fr._needs_rebuild or any(fr._dead(i) for i in range(2))
        ) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert fr.replacements >= 1
    finally:
        fr.close(drain=False)
