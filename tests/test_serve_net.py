"""Network front door (round 19): protocol status taxonomy, the shared
frame codec, single-connection e2e over a real TCP socket, wire-deadline
-> scheduler-timeout propagation, tenant-header routing into the pool,
torn-frame / abrupt-disconnect hygiene (no stranded futures on either
peer), trace telescoping across the wire, and the slow-gated open-loop
harness gate.

Tier-1 here is one module-scoped worker server plus worker-less
pump-driven servers (no subprocesses, scale-6 graph); the process-fleet
open-loop representatives are ``slow``.
"""

import socket
import struct
import time

import numpy as np
import pytest

from combblas_tpu import obs
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import (
    BackpressureError,
    CircuitBreakerOpen,
    EnginePool,
    GraphEngine,
    IpcTimeoutError,
    NetClient,
    NetFrontend,
    ReplicaDeadError,
    ServeConfig,
)
from combblas_tpu.serve import frame
from combblas_tpu.serve.net import protocol as P
from combblas_tpu.utils.rmat import rmat_symmetric_coo_host


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


SCALE = 6
N = 1 << SCALE


def _wait(cond, timeout=10.0, tick=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return cond()


@pytest.fixture(scope="module")
def graph():
    rows, cols = rmat_symmetric_coo_host(11, SCALE, 4)
    return rows, cols


@pytest.fixture(scope="module")
def engine(graph):
    rows, cols = graph
    return GraphEngine.from_coo(
        Grid.make(1, 1), rows, cols, N, kinds=("bfs",)
    )


@pytest.fixture(scope="module")
def live_roots(graph):
    rows, _ = graph
    deg = np.bincount(rows, minlength=N)
    return np.flatnonzero(deg > 0).astype(np.int32)


@pytest.fixture(scope="module")
def served(engine):
    """One worker server behind one frontend, warm, shared by the fast
    e2e tests (module scope keeps the compile cost paid once)."""
    srv = engine.serve(
        ServeConfig(
            lane_widths=(1, 2), max_wait_s=0.002,
            update_autostart=False,
        )
    )
    srv.start()
    srv.warmup(widths=(1, 2))
    fe = NetFrontend(srv)
    yield srv, fe
    fe.close()
    srv.close()


# --- protocol taxonomy (pure, no sockets) -----------------------------------


def test_wire_status_taxonomy_round_trip():
    """Every taxonomy member maps to its typed status and rebuilds as
    the SAME exception type client-side (the docstring table in
    serve/net/protocol.py, bijectively)."""
    cases = [
        (CircuitBreakerOpen("bfs", 0.5, tenant="web"),
         P.ST_BREAKER_OPEN, CircuitBreakerOpen),
        (BackpressureError(7, 0.01, tenant="web"),
         P.ST_BACKPRESSURE, BackpressureError),
        (ReplicaDeadError("all replicas failed"),
         P.ST_REPLICA_DEAD, ReplicaDeadError),
        (TimeoutError("deadline"), P.ST_TIMEOUT, TimeoutError),
        (IpcTimeoutError("ipc deadline"), P.ST_TIMEOUT, TimeoutError),
        (ValueError("bad root"), P.ST_INVALID, ValueError),
        (KeyError("tenant"), P.ST_INVALID, ValueError),
        (RuntimeError("boom"), P.ST_UNAVAILABLE, RuntimeError),
    ]
    for exc, status, rebuilt_t in cases:
        msg = P.wire_error(exc, mid=3)
        assert msg["status"] == status, exc
        assert msg["id"] == 3
        assert status in P.ERROR_STATUSES
        assert isinstance(P.wire_exception(msg), rebuilt_t), exc
    # breaker_open wins over backpressure (it IS a subclass): the more
    # specific code must be checked first
    assert isinstance(
        CircuitBreakerOpen("bfs", 0.1), BackpressureError
    )
    m = P.wire_error(CircuitBreakerOpen("bfs", 0.25, tenant="t"))
    assert m["status"] == P.ST_BREAKER_OPEN
    back = P.wire_exception(m)
    assert back.kind == "bfs"
    assert back.retry_after_s == 0.25
    assert back.tenant == "t"
    # retry hints survive the wire round trip
    bp = P.wire_exception(P.wire_error(BackpressureError(9, 0.125)))
    assert bp.retry_after_s == 0.125
    # a NEWER server's unknown status degrades, never crashes
    assert isinstance(
        P.wire_exception({"status": "shiny_new", "error": "x"}),
        RuntimeError,
    )


# --- the shared frame codec -------------------------------------------------


def test_channel_ndarray_round_trip_and_byte_accounting():
    """Binary ndarray replies survive a real socket round trip
    bit-exact, and both peers account whole-frame byte totals."""
    a, b = socket.socketpair()
    ca = frame.Channel(a, peer="net")
    cb = frame.Channel(b, peer="netclient")
    try:
        arr = np.arange(8, dtype=np.int32)
        n = ca.send({"status": "ok", "result": {"levels": arr}})
        assert n > 0
        assert ca.bytes_out == n
        got = cb.recv(timeout=5)
        assert cb.bytes_in == n  # advances only on whole frames
        out = got["result"]["levels"]
        assert isinstance(out, np.ndarray)
        assert out.dtype == np.int32
        np.testing.assert_array_equal(out, arr)
    finally:
        ca.close()
        cb.close()


# --- single-connection e2e --------------------------------------------------


def test_single_connection_e2e(served, live_roots):
    """hello -> ping -> submit (binary ndarray reply, bit-exact vs the
    in-process path) -> submit_many with per-root error isolation ->
    stats/health, then a clean unwind."""
    srv, fe = served
    r0, r1 = int(live_roots[0]), int(live_roots[1])
    direct = srv.submit("bfs", r0).result(timeout=60)
    with NetClient("127.0.0.1", fe.port) as c:
        assert c.server_pooled is False
        assert c.ping()["pong"] is True
        out = c.submit("bfs", r0)
        assert isinstance(out["levels"], np.ndarray)
        assert out["levels"].dtype == np.int32
        np.testing.assert_array_equal(out["levels"], direct["levels"])
        np.testing.assert_array_equal(
            out["parents"], direct["parents"]
        )
        # per-root failure isolation survives the wire: the bad root
        # is a typed per-entry status, not a torn batch
        many = c.submit_many("bfs", [r0, N + 99])
        assert many[0]["status"] == P.ST_OK
        np.testing.assert_array_equal(
            many[0]["result"]["levels"], direct["levels"]
        )
        assert many[1]["status"] == P.ST_INVALID
        assert isinstance(
            P.wire_exception(many[1]), ValueError
        )
        st = c.stats()
        assert st["net"]["connections"] == 1
        assert st["net"]["port"] == fe.port
        assert "backend" in st
        h = c.health()
        assert h["status"] == "ok"
        assert h["net"]["closing"] is False
    assert _wait(lambda: fe.stats()["net"]["connections"] == 0)


def test_submit_update_shares_the_protocol(graph):
    """The write lane rides the same connection: an edge insert over
    the wire merges (pump-driven) and subsequent reads see it."""
    rows, cols = graph
    eng = GraphEngine.from_coo(
        Grid.make(1, 1), rows, cols, N, kinds=("bfs",),
        keep_coo=True,  # the mutation lane needs the host edge list
    )
    srv = eng.serve(ServeConfig(
        lane_widths=(1,), update_autostart=False, update_flush=100,
    ))
    v0 = eng.version_id
    fe = NetFrontend(srv)
    try:
        present = set(zip(rows.tolist(), cols.tolist()))
        a, b = next(
            (i, j) for i in range(N) for j in range(N)
            if i != j and (i, j) not in present
        )
        with NetClient("127.0.0.1", fe.port) as c:
            fut = c.submit_update_nowait(
                [("insert", a, b), ("insert", b, a)]
            )
            assert _wait(lambda: srv.stats()["updates"]["pending"] > 0)
            assert srv.pump_updates(force=True) == 2
            res = fut.result(timeout=30)
            assert res["version"] == v0 + 1
    finally:
        fe.close()
        srv.close()


# --- wire deadline -> scheduler timeout -------------------------------------


def test_wire_deadline_becomes_scheduler_timeout(engine, live_roots):
    """``deadline_s`` on the wire is the scheduler's per-request
    timeout: the request expires IN QUEUE (the deadline sweep, not a
    client-side timer) and comes back as a typed ``timeout`` reply."""
    srv = engine.serve(ServeConfig(
        lane_widths=(4,), max_wait_s=60.0, update_autostart=False,
    ))
    fe = NetFrontend(srv)
    try:
        with NetClient("127.0.0.1", fe.port) as c:
            fut = c.submit_nowait(
                "bfs", int(live_roots[0]), deadline_s=0.001
            )
            assert _wait(lambda: srv.scheduler.depth() == 1)
            time.sleep(0.01)
            srv.pump()  # deadline sweep fails the overdue request
            with pytest.raises(TimeoutError):
                fut.result(timeout=10)
            # a non-positive deadline is a typed invalid reply
            bad = c.submit_nowait(
                "bfs", int(live_roots[0]), deadline_s=-1.0
            )
            with pytest.raises(ValueError, match="deadline_s"):
                bad.result(timeout=10)
    finally:
        fe.close()
        srv.close()


def test_slo_deadline_still_caps_wire_deadline(engine, live_roots):
    """A generous wire deadline cannot LOOSEN the server's SLO budget:
    ``slo_deadline_s`` caps the admitted timeout."""
    srv = engine.serve(ServeConfig(
        lane_widths=(4,), max_wait_s=60.0, slo_deadline_s=0.001,
        update_autostart=False,
    ))
    fe = NetFrontend(srv)
    try:
        with NetClient("127.0.0.1", fe.port) as c:
            fut = c.submit_nowait(
                "bfs", int(live_roots[0]), deadline_s=60.0
            )
            assert _wait(lambda: srv.scheduler.depth() == 1)
            time.sleep(0.01)
            srv.pump()
            with pytest.raises(TimeoutError):
                fut.result(timeout=10)
    finally:
        fe.close()
        srv.close()


# --- admission rejections as wire replies -----------------------------------


def test_backpressure_is_a_typed_wire_reply(engine, live_roots):
    """A full queue rejects over the wire with ``backpressure`` + the
    retry hint — same type, same fields as the in-process raise — and
    the connection stays open; parked futures settle when the backend
    fails them (never stranded)."""
    srv = engine.serve(ServeConfig(
        lane_widths=(16,), max_queue=2, max_wait_s=60.0,
        update_autostart=False,
    ))
    fe = NetFrontend(srv)
    try:
        with NetClient("127.0.0.1", fe.port) as c:
            r = int(live_roots[0])
            f1 = c.submit_nowait("bfs", r)
            f2 = c.submit_nowait("bfs", r)
            # same connection => frames dispatch in order: by the time
            # the third is admitted the first two hold the queue
            f3 = c.submit_nowait("bfs", r)
            with pytest.raises(BackpressureError) as ei:
                f3.result(timeout=10)
            assert ei.value.retry_after_s > 0
            # the rejection was a REPLY: the connection still serves
            assert c.ping()["pong"] is True
            assert not f1.done() and not f2.done()
            srv.scheduler.fail_pending(RuntimeError("teardown"))
            assert isinstance(
                f1.exception(timeout=10), RuntimeError
            )
            assert isinstance(
                f2.exception(timeout=10), RuntimeError
            )
    finally:
        fe.close()
        srv.close()


def test_connection_limit_is_a_typed_hello_reject(engine):
    """Past ``max_conns`` the hello itself answers ``backpressure``
    (typed reply, then close) — never a silent drop."""
    srv = engine.serve(ServeConfig(
        lane_widths=(1,), update_autostart=False,
    ))
    fe = NetFrontend(srv, max_conns=1)
    try:
        c1 = NetClient("127.0.0.1", fe.port)
        try:
            with pytest.raises(BackpressureError):
                NetClient("127.0.0.1", fe.port)
            assert fe.rejected_conns == 1
            assert c1.ping()["pong"] is True  # the admitted conn lives
        finally:
            c1.close()
    finally:
        fe.close()
        srv.close()


# --- tenant-header routing --------------------------------------------------


def _tenant_coo(seed, n=N, m=240):
    r = np.random.default_rng(seed)
    rows = r.integers(0, n, m)
    cols = r.integers(0, n, m)
    return (
        np.concatenate([rows, cols]), np.concatenate([cols, rows])
    )


def test_tenant_header_routes_to_the_right_graph():
    """The hello's tenant header routes every request on the
    connection to that PoolServer tenant: two clients, two tenants,
    two DIFFERENT graphs answering the same root."""
    pool = EnginePool(Grid.make(1, 1))
    for i, name in enumerate(("a", "b")):
        rows, cols = _tenant_coo(i)
        pool.add_tenant(
            name, rows, cols, N, kinds=("bfs",),
            config=ServeConfig(
                lane_widths=(1,), update_autostart=False
            ),
        )
    psrv = pool.serve()
    psrv.warmup(widths=(1,))
    fe = NetFrontend(psrv)
    ca = cb = None
    try:
        ca = NetClient("127.0.0.1", fe.port, tenant="a")
        cb = NetClient("127.0.0.1", fe.port, tenant="b")
        assert ca.server_pooled is True
        fa = ca.submit_nowait("bfs", 3)
        fb = cb.submit_nowait("bfs", 3)

        def drain():
            while psrv.pump(force=True):
                pass
            return fa.done() and fb.done()

        assert _wait(drain)
        got = {"a": fa.result(timeout=0), "b": fb.result(timeout=0)}
        for t in ("a", "b"):
            direct = pool.engine(t).execute(
                "bfs", np.asarray([3], np.int32)
            )["levels"][:, 0]
            np.testing.assert_array_equal(got[t]["levels"], direct)
        assert not np.array_equal(
            got["a"]["levels"], got["b"]["levels"]
        )
        # unknown tenant / missing tenant: typed hello rejects
        with pytest.raises(ValueError, match="unknown tenant"):
            NetClient("127.0.0.1", fe.port, tenant="nope")
        with pytest.raises(ValueError, match="tenant header required"):
            NetClient("127.0.0.1", fe.port)
    finally:
        for c in (ca, cb):
            if c is not None:
                c.close()
        fe.close()
        psrv.close()


# --- torn frames / abrupt disconnects ---------------------------------------


def test_torn_frame_tears_down_only_that_connection(engine):
    """A length prefix promising bytes that never arrive (and an
    oversized prefix) unwind THAT connection; the listener keeps
    serving."""
    srv = engine.serve(ServeConfig(
        lane_widths=(1,), update_autostart=False,
    ))
    fe = NetFrontend(srv)
    try:
        def raw_hello():
            raw = socket.create_connection(
                ("127.0.0.1", fe.port), timeout=5
            )
            ch = frame.Channel(raw, peer="netclient")
            ch.send({
                "v": P.PROTOCOL_VERSION, "op": "hello", "id": 0,
                "tenant": None,
            })
            assert ch.recv(timeout=5)["status"] == P.ST_OK
            return raw, ch

        raw, _ch = raw_hello()
        assert _wait(
            lambda: fe.stats()["net"]["connections"] == 1
        )
        raw.sendall(struct.pack(">I", 1000) + b"\x00\x01")  # torn
        raw.close()
        assert _wait(
            lambda: fe.stats()["net"]["connections"] == 0
        )
        raw2, _ch2 = raw_hello()
        raw2.sendall(struct.pack(">I", frame.MAX_FRAME + 1))
        assert _wait(
            lambda: fe.stats()["net"]["connections"] == 0
        )
        raw2.close()
        # the front door survived both: a fresh client still serves
        with NetClient("127.0.0.1", fe.port) as c:
            assert c.ping()["pong"] is True
    finally:
        fe.close()
        srv.close()


def test_abrupt_disconnect_strands_no_futures(engine, live_roots):
    """A client vanishing with requests parked in the queue: its
    client-side futures fail with ConnectionError immediately, the
    backend futures still settle server-side, and their replies are
    counted as drops — nothing hangs, nothing leaks."""
    srv = engine.serve(ServeConfig(
        lane_widths=(16,), max_wait_s=60.0, update_autostart=False,
    ))
    fe = NetFrontend(srv)
    try:
        c = NetClient("127.0.0.1", fe.port)
        f1 = c.submit_nowait("bfs", int(live_roots[0]))
        f2 = c.submit_nowait("bfs", int(live_roots[1]))
        assert _wait(lambda: srv.scheduler.depth() == 2)
        c.close()  # abrupt: requests still queued server-side
        assert isinstance(f1.exception(timeout=10), ConnectionError)
        assert isinstance(f2.exception(timeout=10), ConnectionError)
        assert c.pending == 0  # client map torn down, not stranded
        assert _wait(
            lambda: fe.stats()["net"]["connections"] == 0
        )
        drops0 = fe.reply_drops
        srv.scheduler.fail_pending(RuntimeError("drain"))
        # server-side futures settled; replies hit the closed channel
        # and are accounted as drops (stranded futures: zero)
        assert _wait(lambda: fe.reply_drops == drops0 + 2)
        assert srv.scheduler.depth() == 0
    finally:
        fe.close()
        srv.close()


# --- trace telescoping across the wire --------------------------------------


def test_net_trace_telescopes_to_wall(served, live_roots):
    """One sampled request produces ONE schema-trace record whose
    stages run net_accept -> net_read -> [serve stages] -> net_write
    and sum EXACTLY to the end-to-end wall (the hold/release
    contract)."""
    from combblas_tpu.obs import trace as obs_trace

    srv, fe = served
    obs.enable(install_hooks=False)
    prev = obs_trace.sample_rate()
    obs_trace.set_sample_rate(1.0)
    try:
        with NetClient("127.0.0.1", fe.port) as c:
            c.submit("bfs", int(live_roots[0]))
        recs = [
            r for r in obs_trace.records()
            if r["labels"].get("transport") == "net"
        ]
        assert len(recs) == 1
        rec = recs[0]
        stages = [s["stage"] for s in rec["stages"]]
        assert stages[0] == "net_accept"
        assert stages[1] == "net_read"
        assert stages[-1] == "net_write"
        assert {"queue_wait", "assemble", "execute"} <= set(stages)
        assert rec["labels"]["status"] == "ok"
        assert sum(
            s["s"] for s in rec["stages"]
        ) == pytest.approx(rec["wall_s"], rel=1e-6, abs=1e-9)
    finally:
        obs_trace.set_sample_rate(prev)


# --- open-loop harness (slow: subprocess fleet) -----------------------------


@pytest.mark.slow
def test_open_loop_gate_small_fleet():
    """The open-loop harness, scaled down: seeded Poisson arrivals over concurrent connections against
    a 2-replica process fleet — >=99% availability, zero stranded
    futures, zero post-warmup retraces, every failure typed."""
    from combblas_tpu.serve.net import loadgen

    out = loadgen.run(
        rate=50, conns=8, seconds=2, scale=6, edgefactor=4,
        replicas=2,
    )
    assert out["ok"], out
    assert out["availability"] >= 0.99
    assert out["stranded_futures"] == 0
    assert out["retraces_after_warmup"] == 0
    assert out["untyped_failures"] == 0
    assert out["offered_qps"] > 0 and out["achieved_qps"] > 0
    assert out["decomposition"], out  # stitched net/router/ipc tiers


@pytest.mark.slow
@pytest.mark.chaos
def test_open_loop_under_sigkill_chaos():
    """Open loop with a scripted SIGKILL mid-run: failures stay TYPED
    (wire statuses, never hangs or untyped blowups) and no futures
    strand on either peer while the fleet self-heals."""
    from combblas_tpu.serve.net import loadgen

    out = loadgen.run(
        rate=40, conns=8, seconds=3, scale=6, edgefactor=4,
        replicas=2, chaos=True,
    )
    assert out["chaos"] is True
    assert out["untyped_failures"] == 0, out
    assert out["stranded_futures"] == 0
    assert out["availability"] >= 0.9, out


# --- blocking-client retry policy (round 20) --------------------------------


def test_client_retry_policy_unit():
    """The ``_call_retrying`` contract, driven with stub send
    functions (no sockets): backpressure sleeps the server's hint and
    resends until the budget runs out; a send failure (the request
    never left this process) reconnects and resends EVEN for writes;
    an in-flight death resends reads but surfaces to write callers
    (``retry_inflight=False`` — idempotency is theirs)."""
    from concurrent.futures import Future

    cli = NetClient.__new__(NetClient)
    cli.max_retries = 3
    cli.backoff_s = 0.001
    cli.max_backoff_s = 0.004
    cli._closed = False
    reconnects = []
    cli._ensure_connected = lambda: reconnects.append(1)

    def failing(exc, fails, then=None):
        state = {"n": 0}

        def send():
            state["n"] += 1
            fut = Future()
            if state["n"] <= fails:
                fut.set_exception(exc)
            else:
                fut.set_result(then)
            return fut

        return send

    # backpressure: two rejects, then success — inside the budget
    bp = BackpressureError(7, 0.001)
    assert cli._call_retrying(failing(bp, 2, {"ok": 1}), 5.0) == {
        "ok": 1
    }
    # budget exhaustion surfaces the typed error
    with pytest.raises(BackpressureError):
        cli._call_retrying(failing(bp, 99), 5.0)
    # the breaker subclass rides the same lane (its retry_after_s is
    # the cooldown hint)
    brk = CircuitBreakerOpen("bfs", 0.001)
    assert cli._call_retrying(failing(brk, 1, {"ok": 2}), 5.0) == {
        "ok": 2
    }
    # send failure: never left the process — writes resend too
    state = {"n": 0}

    def send_fail_then_ok():
        state["n"] += 1
        if state["n"] == 1:
            raise ConnectionError("send failed")
        fut = Future()
        fut.set_result({"ok": 3})
        return fut

    assert cli._call_retrying(
        send_fail_then_ok, 5.0, retry_inflight=False
    ) == {"ok": 3}
    assert reconnects  # the drop triggered a reconnect
    # in-flight death: reads resend...
    gone = ConnectionError("server gone")
    assert cli._call_retrying(failing(gone, 1, {"ok": 4}), 5.0) == {
        "ok": 4
    }
    # ...writes do not (may have been applied server-side)
    with pytest.raises(ConnectionError):
        cli._call_retrying(failing(gone, 1, {"ok": 5}), 5.0,
                           retry_inflight=False)
    # a closed client never retries
    cli._closed = True
    with pytest.raises(ConnectionError):
        cli._call_retrying(failing(gone, 1, {"ok": 6}), 5.0)
    # max_retries=0 restores fail-fast
    cli._closed = False
    cli.max_retries = 0
    with pytest.raises(BackpressureError):
        cli._call_retrying(failing(bp, 1, {"ok": 7}), 5.0)


def test_client_reconnects_after_connection_drop(served, live_roots):
    """E2E over a real socket: the connection dies under the client
    (channel torn down mid-session); the next blocking submit
    reconnects — new socket, new hello, new reader generation — and
    answers bit-exactly.  The nowait primitives stay fail-fast."""
    srv, fe = served
    root = int(live_roots[0])
    direct = srv.submit("bfs", root).result(timeout=60)
    cli = NetClient("127.0.0.1", fe.port)
    try:
        np.testing.assert_array_equal(
            cli.submit("bfs", root)["levels"], direct["levels"]
        )
        cli.ch.close()  # the drop: every send on this channel fails
        out = cli.submit("bfs", root)
        np.testing.assert_array_equal(out["levels"], direct["levels"])
        assert cli.reconnects >= 1
        assert cli.pending == 0  # no stranded futures across the drop
        # nowait on a freshly-dropped channel surfaces the error
        cli.ch.close()
        with pytest.raises(ConnectionError):
            cli.submit_nowait("bfs", root)
        cli.submit("bfs", root)  # the blocking lane still self-heals
    finally:
        cli.close()
