"""Cross-host sharded serving (round 20, ISSUE 18): row-slab
partitioning, router-driven bulk-synchronous hop loops, the two-phase
per-slice WAL write protocol under a VECTOR checkpoint frontier, and
one-slice quarantine/respawn recovery.

The load-bearing properties:

* BIT-EXACTNESS — a sharded engine answers bfs/sssp identically (same
  parents, same distances, same ``batch_niter``) to the unsharded
  engine it partitions, including after writes and slice deaths;
* CRASH-RECOVERY on the vector frontier — for a crash at every
  append/commit/checkpoint boundary (frontier-skewing partial
  checkpoints and a torn final WAL line included),
  ``ShardedEngine.recover`` reassembles a ``to_host_coo()`` equal to a
  never-crashed engine that applied every fully-appended batch.

Tier-1 runs the local-mode (in-process slices) representatives; the
full boundary sweep and the subprocess SIGKILL/respawn scenario are
``slow``.
"""

import os

import numpy as np
import pytest

from combblas_tpu.dynamic import DeltaBatch
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import GraphEngine, ShardedEngine
from combblas_tpu.serve.shard import ShardSpec, plan_partition, shard_coo

N = 40


def _coo(seed, n=N, m=170):
    r = np.random.default_rng(seed)
    return r.integers(0, n, m), r.integers(0, n, m)


def _absent_pairs(rows, cols, k, n=N):
    present = set(zip(rows.tolist(), cols.tolist()))
    out = []
    for i in range(n):
        for j in range(n):
            if i != j and (i, j) not in present:
                out.append((i, j))
                if len(out) >= k:
                    return out
    return out


def _assert_coo_equal(a, b):
    ra, ca, wa = a
    rb, cb, wb = b
    np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
    if wa is not None or wb is not None:
        np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))


# --- partition planning (pure) ----------------------------------------------


def test_plan_partition_balanced_contiguous():
    """Slabs are contiguous, cover [0, n) exactly, and differ by at
    most one row (the first ``n % p`` slabs take the remainder)."""
    spec = plan_partition(10, 3)
    assert spec.bounds == ((0, 4), (4, 7), (7, 10))
    assert spec.nslices == 3 and spec.ncols == 10
    sizes = [r1 - r0 for r0, r1 in spec.bounds]
    assert max(sizes) - min(sizes) <= 1
    # owner_of maps every row to the slab containing it
    for row in range(10):
        i = spec.owner_of(row)
        r0, r1 = spec.bounds[i]
        assert r0 <= row < r1
    # degenerate edges: one slice works; p > n (an empty slab would
    # serve nothing) and p < 1 are rejected up front
    assert plan_partition(5, 1).bounds == ((0, 5),)
    with pytest.raises(ValueError, match="nslices"):
        plan_partition(3, 8)
    with pytest.raises(ValueError, match="nslices"):
        plan_partition(3, 0)


def test_shard_coo_translates_rows_keeps_cols_global():
    rows = np.array([0, 3, 7, 9, 4])
    cols = np.array([9, 1, 2, 0, 4])
    w = np.array([1.0, 2.0, 3.0, 4.0, 5.0], np.float32)
    spec = plan_partition(10, 2)  # slabs [0,5) and [5,10)
    r0, c0, w0 = shard_coo(spec, 0, rows, cols, w)
    r1, c1, w1 = shard_coo(spec, 1, rows, cols, w)
    np.testing.assert_array_equal(np.sort(r0), [0, 3, 4])
    np.testing.assert_array_equal(np.sort(r1), [2, 4])  # 7-5, 9-5
    # columns stay global (hop operands are full-width vectors)
    assert set(c0.tolist()) == {9, 1, 4}
    assert set(c1.tolist()) == {2, 0}
    assert len(w0) == 3 and len(w1) == 2
    # unweighted passes weights through as None
    _, _, wn = shard_coo(spec, 0, rows, cols, None)
    assert wn is None
    # every edge lands in exactly one slab
    assert len(r0) + len(r1) == len(rows)


def test_sharded_kinds_validated_up_front(tmp_path):
    rows, cols = _coo(3)
    with pytest.raises(ValueError, match="do not decompose"):
        ShardedEngine.build(rows, cols, nrows=N, nslices=2,
                            kinds=("bfs", "mcl"),
                            home=str(tmp_path / "a"))
    with pytest.raises(ValueError, match="symmetric"):
        ShardedEngine.build(
            rows, cols, nrows=N, nslices=2, kinds=("propagate",),
            features=np.ones((N, 3), np.float32), symmetric=False,
            home=str(tmp_path / "b"),
        )
    with pytest.raises(ValueError, match="features"):
        ShardedEngine.build(rows, cols, nrows=N, nslices=2,
                            kinds=("propagate",), symmetric=True,
                            home=str(tmp_path / "c"))


# --- the local-mode tier-1 representative ------------------------------------


def test_local_bit_exact_write_kill_heal_recover(tmp_path):
    """THE fast representative of the sharded serving arc: a 2-slice
    local-mode engine answers bfs bit-exactly vs the unsharded build,
    a two-phase write lands on both (vector frontier advances in
    lockstep), a killed slice heals mid-execute via whole-batch
    replay, and a full service reboot from the home reassembles the
    identical global COO."""
    home = str(tmp_path / "home")
    rows, cols = _coo(7)
    grid = Grid.make(1, 1)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",),
                               keep_coo=True)
    sh = ShardedEngine.build(rows, cols, nrows=N, nslices=2,
                             kinds=("bfs",), home=home, mode="local",
                             warmup=False)
    srcs = np.array([0, 5, 17], np.int32)
    ref = eng.execute("bfs", srcs)
    got = sh.execute("bfs", srcs)
    np.testing.assert_array_equal(np.asarray(ref["parents"]),
                                  got["parents"])
    assert int(ref["batch_niter"]) == int(got["batch_niter"])
    # per-slice residency strictly under the whole graph's
    assert max(sh.version.device_bytes_per_slice) < (
        eng.version.device_bytes()
    )
    # two-phase write: both engines apply the same batch
    (a, b), (a2, b2) = _absent_pairs(rows, cols, 2)
    batch = DeltaBatch.from_ops(
        [("insert", a, b), ("insert", b, a)], start_seq=0
    )
    eng.swap(eng.apply_delta(batch))
    v = sh.apply_delta(batch)
    assert v.frontier == [1, 1]  # every slice stamped, no lag
    assert v.wal_seq == 1
    sh.swap(v)
    got = sh.execute("bfs", srcs)
    ref = eng.execute("bfs", srcs)
    np.testing.assert_array_equal(np.asarray(ref["parents"]),
                                  got["parents"])
    # kill one slice: the next execute heals (respawn from slab
    # snapshot + WAL) and the answer is still bit-exact — the OTHER
    # slice is untouched (recover-one-slice)
    survivor = sh.slices[1]
    sh.slices[0].kill()
    got = sh.execute("bfs", srcs)
    np.testing.assert_array_equal(np.asarray(ref["parents"]),
                                  got["parents"])
    assert sh.replacements == 1
    assert sh.slices[1] is survivor
    # a post-heal write keeps the lineage moving
    batch2 = DeltaBatch.from_ops(
        [("insert", a2, b2), ("insert", b2, a2)], start_seq=2
    )
    sh.swap(sh.apply_delta(batch2))
    coo_before = sh.to_host_coo()
    # whole-service reboot from the files alone
    sh.close()
    sh2 = ShardedEngine.recover(home, mode="local")
    assert sh2.version.frontier == [3, 3]
    _assert_coo_equal(coo_before, sh2.to_host_coo())
    got = sh2.execute("bfs", srcs)
    assert got["parents"].shape == np.asarray(ref["parents"]).shape
    sh2.close()


# --- crash-at-every-boundary recovery on the vector frontier -----------------


def _mk_batches(rows, cols, k):
    pairs = _absent_pairs(rows, cols, k)
    return [
        DeltaBatch.from_ops(
            [("insert", a, b), ("insert", b, a)], start_seq=2 * i
        )
        for i, (a, b) in enumerate(pairs)
    ]


def _wal_begin_payload(batch):
    return {
        "first_seq": int(batch.first_seq),
        "rows": np.asarray(batch.rows, np.int64),
        "cols": np.asarray(batch.cols, np.int64),
        "vals": np.asarray(batch.vals, np.float32),
        "ops": np.asarray(batch.ops, np.int8),
    }


def _crash_scenario(tmp_path, tag, n_commit, n_append_only,
                    commit_partial, ckpt, torn):
    """Build a 2-slice local service, fully apply ``n_commit``
    batches, durably APPEND (phase 1 only — crash before phase 2)
    ``n_append_only`` more, optionally commit the first appended batch
    on slice 0 only (``commit_partial`` — the mid-_commit_all crash),
    checkpoint one slice mid-stream (``ckpt = (slice, after_batch)`` —
    the vector-frontier skew), optionally tear a partial final line
    onto slice 0's log — then crash (kill, no close) and recover.

    Every fully-appended batch is durable on every slice, so the
    recovered engine must be ``to_host_coo``-equal to a NEVER-CRASHED
    twin that applied them all; a torn line was never acknowledged and
    must vanish."""
    home = str(tmp_path / f"crash-{tag}")
    rows, cols = _coo(11)
    batches = _mk_batches(rows, cols, n_commit + n_append_only)
    sh = ShardedEngine.build(rows, cols, nrows=N, nslices=2,
                             kinds=("bfs",), home=home, mode="local",
                             warmup=False)
    for k, batch in enumerate(batches):
        if k < n_commit:
            sh.swap(sh.apply_delta(batch))
        else:
            for sl in sh.slices:  # phase 1 everywhere, then crash
                sl.call("wal_begin", _wal_begin_payload(batch))
            if commit_partial and k == n_commit:
                payload = _wal_begin_payload(batch)
                payload["last_seq"] = int(batch.last_seq)
                sh.slices[0].call("wal_commit", payload)
        if ckpt is not None and k + 1 == ckpt[1]:
            sh.slices[ckpt[0]].call("checkpoint_now",
                                    {"reason": "test"})
    if torn:
        wal_path = os.path.join(home, "slice0", "wal.jsonl")
        assert os.path.exists(wal_path)
        with open(wal_path, "a") as f:
            f.write('{"v": "combblas_tpu.wal/v1", "first_se')
    for sl in sh.slices:  # CRASH: the files are all that survives
        sl.kill()
    recovered = ShardedEngine.recover(home, mode="local")
    # the never-crashed twin: every fully-appended batch applied
    ref = ShardedEngine.build(rows, cols, nrows=N, nslices=2,
                              kinds=("bfs",),
                              home=str(tmp_path / f"ref-{tag}"),
                              mode="local", warmup=False)
    for batch in batches:
        ref.swap(ref.apply_delta(batch))
    _assert_coo_equal(recovered.to_host_coo(), ref.to_host_coo())
    # the vector frontier re-converged at the last appended seq
    last = int(batches[-1].last_seq) if batches else -1
    assert recovered.version.frontier == [last, last]
    recovered.close()
    ref.close()


def test_crash_recovery_fast_representative(tmp_path):
    """One tier-1 scenario covering every boundary class at once:
    committed prefix, appended-uncommitted tail, a partial commit on
    one slice, a one-slice checkpoint (frontier skew) and the torn
    final line."""
    _crash_scenario(tmp_path, "fast", n_commit=2, n_append_only=1,
                    commit_partial=True, ckpt=(1, 1), torn=True)


@pytest.mark.slow
def test_crash_recovery_bit_exact_at_every_boundary(tmp_path):
    """THE acceptance sweep: crash after every append/commit/
    checkpoint boundary combination — committed-only, appended-only,
    partial commits, checkpoints skewing either slice's frontier at
    every position, torn tails — each recovers ``to_host_coo``-equal
    with its never-crashed twin."""
    cases = []
    for n_commit, n_append in ((1, 0), (0, 1), (2, 1), (1, 2)):
        for partial in ({False, n_append > 0}):
            ck_positions = [None] + [
                (s, p) for s in (0, 1)
                for p in range(1, n_commit + n_append + 1)
            ]
            for ckpt in ck_positions:
                cases.append((n_commit, n_append, partial, ckpt,
                              False))
    cases.append((2, 1, True, (1, 2), True))
    cases.append((0, 2, False, None, True))
    for i, (nc, na, partial, ckpt, torn) in enumerate(cases):
        _crash_scenario(tmp_path, str(i), n_commit=nc,
                        n_append_only=na, commit_partial=partial,
                        ckpt=ckpt, torn=torn)


# --- subprocess fleet: SIGKILL + respawn (slow) -------------------------------


@pytest.mark.slow
@pytest.mark.chaos
def test_process_mode_sigkill_respawn_bit_exact(tmp_path):
    """Real subprocess slices: bfs AND sssp bit-exact vs unsharded,
    one slice SIGKILLed mid-service respawns from its slab snapshot +
    WAL while the other keeps its devices, answers stay bit-exact and
    the respawn costs ZERO post-warmup retraces."""
    rng = np.random.default_rng(1)
    n, m = 48, 300
    rows = rng.integers(0, n, m)
    cols = rng.integers(0, n, m)
    w = rng.random(m).astype(np.float32) + 0.1
    grid = Grid.make(1, 1)
    eng = GraphEngine.from_coo(grid, rows, cols, nrows=n, weights=w,
                               kinds=("bfs", "sssp"), keep_coo=True)
    sh = ShardedEngine.build(
        rows, cols, nrows=n, nslices=2, weights=w,
        kinds=("bfs", "sssp"), home=str(tmp_path / "proc"),
        mode="process", warmup=True, warmup_widths=(4,),
    )
    try:
        srcs = np.array([0, 5, 17, 40], np.int32)
        for kind, key in (("bfs", "parents"), ("sssp", "dist")):
            ref = eng.execute(kind, srcs)
            got = sh.execute(kind, srcs)
            np.testing.assert_array_equal(np.asarray(ref[key]),
                                          got[key])
        mark = sh.trace_mark()
        sh.slices[0].kill()  # SIGKILL; next execute heals + replays
        got = sh.execute("bfs", srcs)
        ref = eng.execute("bfs", srcs)
        np.testing.assert_array_equal(np.asarray(ref["parents"]),
                                      got["parents"])
        assert sh.replacements == 1
        assert sh.retraces_since(mark) == 0
    finally:
        sh.close()


def test_spec_owner_of_rejects_out_of_range():
    spec = ShardSpec(nrows=10, ncols=10, bounds=((0, 5), (5, 10)))
    with pytest.raises(ValueError):
        spec.owner_of(10)
    with pytest.raises(ValueError):
        spec.owner_of(-1)


# --- the round-21 wire protocol: encodings, resident state, epochs -----------


def test_encoding_equivalence_fast_representative(tmp_path):
    """ISSUE 19: THE fast representative of the wire-protocol sweep —
    one 2-slice local engine answers bfs/sssp bit-exactly vs the
    unsharded build under FORCED sparse, forced dense, and auto
    encodings (the router's per-hop choice mixes regimes mid-batch),
    and the per-execute wire accounting shows sparse strictly cheaper
    than dense on hop payloads."""
    rows, cols = _coo(11)
    w = (np.random.default_rng(11).random(rows.shape[0])
         .astype(np.float32) + 0.1)
    grid = Grid.make(1, 1)
    eng = GraphEngine.from_coo(grid, rows, cols, nrows=N, weights=w,
                               kinds=("bfs", "sssp"))
    sh = ShardedEngine.build(
        rows, cols, nrows=N, nslices=2, weights=w,
        kinds=("bfs", "sssp"), home=str(tmp_path / "enc"),
        mode="local", warmup=False,
    )
    try:
        srcs = np.array([0, 5, 17], np.int32)
        refs = {k: eng.execute(k, srcs) for k in ("bfs", "sssp")}
        hop_payload = {}
        for mode in ("sparse", "dense", "auto"):
            sh.frontier_mode = mode  # the router owns the decision
            for kind, keys in (("bfs", ("parents", "levels")),
                               ("sssp", ("dist",))):
                got = sh.execute(kind, srcs)
                for key in keys:
                    np.testing.assert_array_equal(
                        np.asarray(refs[kind][key]), got[key],
                        err_msg=f"{kind}/{key} under {mode}",
                    )
                assert (int(got["batch_niter"])
                        == int(refs[kind]["batch_niter"])), mode
                st = sh.last_exec_stats
                assert st["collects"] == 1
                assert len(st["frontier_nnz"]) == st["hops"]
                if mode in ("sparse", "dense"):
                    assert set(st["enc_hops"]) == {mode}
                    hop_payload[(kind, mode)] = st["bytes_by_enc"][mode]
        # auto mixed regimes on this graph (frontier starts tiny,
        # saturates mid-batch, then dries up)
        assert set(sh.last_exec_stats["enc_hops"]) == {"sparse",
                                                       "dense"}
        for kind in ("bfs", "sssp"):
            assert (hop_payload[(kind, "sparse")]
                    < hop_payload[(kind, "dense")])
    finally:
        sh.close()


def test_stale_epoch_replay_reseeds_resident_state(tmp_path):
    """ISSUE 19: a slice that loses its resident loop state mid-batch
    (amnesia respawn between hops) reports StaleEpochError — a
    PROTOCOL fact from a healthy slice, not a death — and the router
    replays the whole batch under a fresh epoch, re-seeding every
    slice, WITHOUT quarantining the reporter.  The replayed answer is
    bit-exact."""
    from combblas_tpu.serve.policy import StaleEpochError

    rows, cols = _coo(13)
    grid = Grid.make(1, 1)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",))
    sh = ShardedEngine.build(
        rows, cols, nrows=N, nslices=2, kinds=("bfs",),
        home=str(tmp_path / "stale"), mode="local", warmup=False,
        frontier="sparse",
    )
    try:
        srcs = np.array([0, 5, 17], np.int32)
        epoch0 = sh._epoch
        orig_fan = sh._fan_hop
        state = {"fans": 0, "stale": 0}

        def fan(kind, payload, **kw):
            if kw.get("op", "hop") == "hop" and state["fans"] == 2:
                # between hops 2 and 3: slice 0 respawns with no
                # resident state (the mid-batch SIGKILL analog)
                sh.slices[0].rt = sh.slices[0]._factory(recover=True)
            state["fans"] += 1
            try:
                return orig_fan(kind, payload, **kw)
            except StaleEpochError:
                state["stale"] += 1
                raise

        sh._fan_hop = fan
        got = sh.execute("bfs", srcs)
        ref = eng.execute("bfs", srcs)
        assert state["stale"] == 1
        assert not sh._needs_rebuild  # reporter was NOT quarantined
        # the replay ran under a FRESH epoch (failed attempt's state
        # can never leak into it)
        assert sh._epoch >= epoch0 + 2
        np.testing.assert_array_equal(np.asarray(ref["parents"]),
                                      got["parents"])
        np.testing.assert_array_equal(np.asarray(ref["levels"]),
                                      got["levels"])
        assert int(got["batch_niter"]) == int(ref["batch_niter"])
    finally:
        sh._fan_hop = orig_fan
        sh.close()


@pytest.mark.slow
def test_encoding_equivalence_sweep(tmp_path):
    """ISSUE 19 (slow twin): the full encoding-equivalence property
    sweep — kinds x widths {1, 4, 16} x {2, 3} slices, forced sparse
    vs forced dense vs auto, all bit-exact vs unsharded (propagate
    allclose, plus the opt-in bf16 wire within its quantization
    budget and the hops==0 final-fan edge)."""
    rng = np.random.default_rng(21)
    n, m = 48, 300
    r0 = rng.integers(0, n, m // 2)
    c0 = rng.integers(0, n, m // 2)
    rows = np.concatenate([r0, c0])   # symmetric: propagate-legal
    cols = np.concatenate([c0, r0])
    w = rng.random(rows.shape[0]).astype(np.float32) + 0.1
    feats = rng.normal(size=(n, 5)).astype(np.float32)
    grid = Grid.make(1, 1)
    eng = GraphEngine.from_coo(
        grid, rows, cols, nrows=n, weights=w, features=feats,
        symmetric=True, kinds=("bfs", "sssp", "propagate"),
    )
    for nslices in (2, 3):
        sh = ShardedEngine.build(
            rows, cols, nrows=n, nslices=nslices, weights=w,
            features=feats, symmetric=True,
            kinds=("bfs", "sssp", "propagate"),
            home=str(tmp_path / f"s{nslices}"), mode="local",
            warmup=False,
        )
        try:
            for width in (1, 4, 16):
                srcs = rng.integers(0, n, width).astype(np.int32)
                refs = {k: eng.execute(k, srcs)
                        for k in ("bfs", "sssp", "propagate")}
                for mode in ("sparse", "dense", "auto"):
                    sh.frontier_mode = mode
                    for kind, keys in (("bfs", ("parents", "levels")),
                                       ("sssp", ("dist",))):
                        got = sh.execute(kind, srcs)
                        for key in keys:
                            np.testing.assert_array_equal(
                                np.asarray(refs[kind][key]), got[key],
                                err_msg=f"{nslices}sl/{kind}/{key}"
                                        f"/w{width}/{mode}",
                            )
                        assert (int(got["batch_niter"])
                                == int(refs[kind]["batch_niter"]))
                ref_f = np.asarray(refs["propagate"]["features"])
                for wire in ("f32", "bf16"):
                    sh.wire = wire
                    got = sh.execute("propagate", srcs)
                    tol = 1e-5 if wire == "f32" else 3e-2
                    np.testing.assert_allclose(
                        ref_f, got["features"], rtol=tol, atol=tol,
                        err_msg=f"{nslices}sl/propagate/w{width}"
                                f"/{wire}",
                    )
                sh.wire = "f32"
            # hops==0 edge: the seed rides the final fan
            sh.propagate_hops = 0
            got = sh.execute("propagate",
                             np.array([0, 1], np.int32))
            assert got["features"].shape == (feats.shape[1], 2)
            sh.propagate_hops = eng.propagate_hops \
                if hasattr(eng, "propagate_hops") else 2
        finally:
            sh.close()
