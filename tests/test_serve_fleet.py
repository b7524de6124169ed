"""Replicated serving fleet + GraphVersion checkpoints (round 14):
least-loaded routing with spillover, home-replica writes fanned out
through the atomic swap, a cold against a warmed replica, and the
``save_version``/``load_version`` zero-retrace warm start.

Tier-1 tests are small and pump/worker-deterministic; the threaded
mixed read/write fleet soak is ``slow``.
"""

import os

import numpy as np
import pytest

from combblas_tpu.parallel.grid import Grid
from combblas_tpu.serve import (
    BackpressureError,
    FleetRouter,
    GraphEngine,
    ServeConfig,
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


@pytest.fixture(scope="module")
def grid():
    return Grid.make(2, 4)


# --- checkpoint round-trip ---------------------------------------------------


def test_checkpoint_roundtrip_bit_identical_and_zero_retrace(
    grid, tmp_path
):
    """The ISSUE-12 regression: ``load_version`` -> ``swap`` -> warmed
    kinds produce ZERO retraces, with every bucket array (including
    the headroom-resolved padding rows) bit-identical to the saved
    version."""
    rows, cols = _coo(3)
    eng = GraphEngine.from_coo(
        grid, rows, cols, N, kinds=("bfs", "pagerank"),
        keep_coo=True, headroom=0.5,
    )
    eng.warmup(widths=(1, 4))
    path = os.path.join(tmp_path, "v.npz")
    checkpoint.save_version(path, eng.version)
    v2 = checkpoint.load_version(path, grid)

    # shapes/dtypes/values bit-identical, headroom included
    assert v2.headroom == eng.version.headroom == 0.5
    for nm in ("E", "P_ell"):
        M1, M2 = getattr(eng.version, nm), getattr(v2, nm)
        assert len(M1.buckets) == len(M2.buckets)
        for b1, b2 in zip(M1.buckets, M2.buckets):
            for a1, a2 in zip(b1, b2):
                assert a1.shape == a2.shape
                assert a1.dtype == a2.dtype
                np.testing.assert_array_equal(
                    np.asarray(a1), np.asarray(a2)
                )
    np.testing.assert_array_equal(
        np.asarray(eng.version.dangling.blocks),
        np.asarray(v2.dangling.blocks),
    )
    # the host COO rode along (the write lane stays available)
    assert v2.host_coo is not None

    mark = eng.trace_mark()
    eng.swap(v2)
    r1 = eng.execute("bfs", np.asarray([3], np.int32))
    eng.execute("pagerank", np.asarray([3, 4, 5, 6], np.int32))
    assert eng.retraces_since(mark) == 0  # the warm-start guarantee
    # and a FRESH engine built on the snapshot answers identically
    eng3 = GraphEngine(grid, version=checkpoint.load_version(path, grid),
                       kinds=("bfs", "pagerank"))
    r3 = eng3.execute("bfs", np.asarray([3], np.int32))
    np.testing.assert_array_equal(r1["levels"], r3["levels"])


def test_checkpoint_guards(grid, tmp_path):
    rows, cols = _coo(4)
    eng = GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",))
    path = os.path.join(tmp_path, "v.npz")
    checkpoint.save_version(path, eng.version)
    # cross-grid restore is refused (re-bucketing would forfeit the
    # bit-identical shapes the zero-retrace guarantee needs)
    with pytest.raises(ValueError, match="SAME grid shape"):
        checkpoint.load_version(path, Grid.make(1, 1))
    # a non-version npz is refused by schema, never guessed at
    other = os.path.join(tmp_path, "other.npz")
    checkpoint.save(other, _spmat(grid))
    with pytest.raises(ValueError, match="GraphVersion"):
        checkpoint.load_version(other, grid)


def _spmat(grid):
    from combblas_tpu.parallel.spmat import SpParMat

    r = np.arange(8) % 4
    return SpParMat.from_global_coo(
        grid, r, r, np.ones(8, np.float32), 8, 8
    )


# --- routing + spillover -----------------------------------------------------


def test_fleet_routes_least_loaded_and_spills(grid):
    """Queries spread over replicas; when one replica's queue is full
    the router SPILLS to the next, and only a fleet-wide full raises
    (the last replica's tenant-named error)."""
    rows, cols = _coo(5)
    cfg = ServeConfig(lane_widths=(1, 2), max_queue=2,
                      max_wait_s=30.0)
    fr = FleetRouter.build(
        grid, rows, cols, N, replicas=2, config=cfg, kinds=("bfs",),
        start=False,  # worker-less: queues fill deterministically
    )
    futs = [fr.submit("bfs", 1) for _ in range(4)]  # 2 per replica
    assert all(
        s.scheduler.depth() == 2 for s in fr.replicas
    )
    with pytest.raises(BackpressureError):
        fr.submit("bfs", 1)
    assert fr.spillovers >= 1
    assert sum(fr.submitted) == 4
    # submit_many: rejected roots fail their OWN futures, no strand
    many = fr.submit_many("bfs", [1, 2])
    assert all(
        isinstance(f.exception(timeout=0), BackpressureError)
        for f in many
    )
    for s in fr.replicas:
        s.scheduler.fail_pending(RuntimeError("teardown"))
    del futs


def test_fleet_write_home_and_fanout(grid):
    """A write routes to the HOME replica; after its merge the new
    version fans out through the atomic swap, so a query about the
    new edge answers correctly on EVERY replica."""
    rows, cols = _coo(6)
    cfg = ServeConfig(lane_widths=(1, 2), update_flush=1,
                      update_max_delay_s=0.005)
    with FleetRouter.build(
        grid, rows, cols, N, replicas=2, config=cfg, kinds=("bfs",),
    ) as fr:
        fr.warmup(widths=(1, 2))
        # pick an edge absent everywhere
        present = set(zip(*map(np.ndarray.tolist, (rows, cols))))
        a, b = next(
            (i, j) for i in range(N) for j in range(N)
            if i != j and (i, j) not in present
            and (j, i) not in present
        )
        vids = [s.engine.version_id for s in fr.replicas]
        res = fr.submit_update(
            [("insert", a, b), ("insert", b, a)]
        ).result(timeout=120)
        assert res["fanned_out"] == 1
        for s, v0 in zip(fr.replicas, vids):
            assert s.engine.version_id == v0 + 1
        # the new edge is visible on BOTH replicas: b is exactly one
        # hop from a (query each replica directly, bypassing routing)
        for s in fr.replicas:
            lev = s.submit("bfs", a).result(timeout=120)["levels"]
            assert lev[b] == 1
    assert fr.fanouts == 1


# --- cold against warm replica -----------------------------------------------


def test_fleet_cold_vs_warm_replica_ab(grid):
    """The fleet A/B: a cold replica serving a lane retraces, while a
    warm-started replica (``warmup()``) reaches zero-retrace steady
    state before its first request."""
    rows, cols = _coo(7)

    def build():
        return GraphEngine.from_coo(grid, rows, cols, N, kinds=("bfs",))

    donor = build()
    donor.plan("bfs", 4)  # the traffic mix's lane

    # COLD replica: no warmup — first width-4 batch must trace
    cold = build()
    mark = cold.trace_mark()
    cold.execute("bfs", np.full(4, -1, np.int32))
    assert cold.retraces_since(mark) > 0

    # WARM replica: the lane is among the warmed widths -> zero
    # retraces at steady state
    warm = build()
    warmed = warm.warmup()
    assert ("bfs", 4) in warmed
    mark = warm.trace_mark()
    warm.execute("bfs", np.full(4, -1, np.int32))
    assert warm.retraces_since(mark) == 0


# --- threaded soak -----------------------------------------------------------


@pytest.mark.slow
def test_fleet_threaded_reads_under_writes(grid):
    """Mixed fleet load: reads spread over both replicas while writes
    stream through the home replica and fan out — every read settles,
    every write lands fleet-wide, no stranded futures."""
    import threading

    rows, cols = _coo(8)
    cfg = ServeConfig(lane_widths=(1, 2, 4), max_queue=256,
                      max_wait_s=0.005, update_flush=2,
                      update_max_delay_s=0.01)
    with FleetRouter.build(
        grid, rows, cols, N, replicas=2, config=cfg, kinds=("bfs",),
    ) as fr:
        fr.warmup(widths=(1, 2, 4))
        write_futs = []

        def writer():
            for k in range(6):
                a, b = 1 + k, 40 + k
                write_futs.append(fr.submit_update(
                    [("insert", a, b), ("insert", b, a)]
                ))

        wt = threading.Thread(target=writer)
        wt.start()
        read_futs = []
        for i in range(60):
            try:
                read_futs.append(fr.submit("bfs", i % N))
            except BackpressureError:
                pass
        wt.join(60)
        assert read_futs
        for f in read_futs:
            assert f.result(timeout=120) is not None
        for f in write_futs:
            assert f.result(timeout=120)["fanned_out"] == 1
    st = fr.stats()
    assert st["fanouts"] == len(write_futs)
    assert sum(st["routed"]) == len(read_futs)
