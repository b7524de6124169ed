"""Smallest obs-wired bench entrypoint: exercise every instrumented hot
path on a tiny R-MAT graph and write one schema-versioned JSONL trace.

    JAX_PLATFORMS=cpu python benchmarks/obs_smoke.py [out.jsonl]

(`--stitched` runs ``run_stitched`` instead: the smallest
CROSS-PROCESS stitched-trace entrypoint — round 18.)

The trace contains, end to end (docs/observability.md has the schema):

  * per-hop BFS spans with ``frontier`` nnz events
    (``models/bfs.py:bfs_levels_instrumented``),
  * SpGEMM symbolic + realized fill-in counters and the per-tile
    LoadImbalance gauge (``parallel/spgemm.py``),
  * redistribute drop counts / retry counters
    (``parallel/redistribute.py:from_device_coo``),
  * compile-cache hit/miss counters (the jax.monitoring bridge; a tiny
    probe program is compiled, evicted from the in-process jit cache,
    and recompiled so the persistent cache registers a genuine hit),
  * kernel dispatch/trace counters (``spmv.dispatch``, ``trace.*``) and
    the BFS lru-cache gauges,
  * a SERVE-PATH request trace (round 15): a worker-less ``Server``
    pumps a handful of BFS queries at sample rate 1.0, so the dump
    carries schema-``trace`` records whose stage durations (queue wait
    -> assemble -> execute -> scatter) sum to each request's
    end-to-end latency — the smallest end-to-end latency-decomposition
    entrypoint.

tests/test_obs.py runs this in-process (2x2 grid under the 8-virtual-
device fixture) and validates the file against the documented schema —
the acceptance gate for the telemetry subsystem. ``DEVICE_SYNC`` is on
here (realized-fill-in metrics need readbacks): this entrypoint is a
CPU/diagnostic tool, never part of a timed chip protocol.
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

SCALE = int(os.environ.get("BENCH_SCALE", "8"))
EDGEFACTOR = int(os.environ.get("BENCH_EDGEFACTOR", "8"))


def run(scale: int = SCALE, edgefactor: int = EDGEFACTOR,
        out_path: str | None = None, grid_shape=(1, 1),
        cache_dir: str | None = None) -> str:
    """Run the instrumented pipeline; returns the JSONL path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from combblas_tpu import obs
    from combblas_tpu.models.bfs import bfs_levels_instrumented
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.parallel.redistribute import from_device_coo
    from combblas_tpu.parallel.spgemm import spgemm_scan
    from combblas_tpu.semiring import PLUS_TIMES, SELECT2ND_MAX
    from combblas_tpu.utils.compile_cache import enable_compile_cache
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    if out_path is None:
        out_path = os.path.join(tempfile.gettempdir(), "obs_smoke.jsonl")
    obs.enable(jsonl_path=out_path, device_sync=True)

    # persistent compile cache so cache hit/miss events fire: the dir
    # the caller names, else the one the process already committed to
    # (or the fixed default / JAX_COMPILATION_CACHE_DIR placement) —
    # never a fresh temporary name, which could never hit
    enable_compile_cache(cache_dir)

    with obs.span("obs_smoke", scale=scale, edgefactor=edgefactor):
        # compile-cache probe: compile, drop the in-process executable,
        # recompile — the second compile is a persistent-cache HIT
        probe = jax.jit(lambda v: (v * 2 + 1).sum())
        float(probe(jnp.arange(64.0)))
        jax.clear_caches()
        float(probe(jnp.arange(64.0)))

        # kernel 1 (host generate + device route): redistribute counters
        n = 1 << scale
        rows, cols = rmat_symmetric_coo_host(42, scale, edgefactor)
        key = rows.astype(np.int64) * n + cols
        uniq = np.unique(key)
        rows_u = (uniq // n).astype(np.int32)
        cols_u = (uniq % n).astype(np.int32)
        grid = Grid.make(*grid_shape)
        ndev = grid.pr * grid.pc
        chunk = -(-len(rows_u) // ndev)
        pad = chunk * ndev - len(rows_u)
        r3 = np.concatenate([rows_u, np.full(pad, n, np.int32)])
        c3 = np.concatenate([cols_u, np.full(pad, n, np.int32)])
        shape = (grid.pr, grid.pc, chunk)
        rdev = jax.device_put(r3.reshape(shape), grid.tile_sharding())
        cdev = jax.device_put(c3.reshape(shape), grid.tile_sharding())
        vdev = jnp.ones(shape, jnp.float32)
        A = from_device_coo(grid, rdev, cdev, vdev, n, n, slack=2.0)

        # SpGEMM (A²): symbolic/realized fill-in + load imbalance
        with obs.span("smoke.spgemm"):
            spgemm_scan(PLUS_TIMES, A, A)

        # per-hop instrumented BFS from the first non-isolated vertex
        deg = np.bincount(rows_u, minlength=n)
        source = int(np.flatnonzero(deg > 0)[0])
        with obs.span("smoke.bfs"):
            parents, levels, niter = bfs_levels_instrumented(
                A, source, sr=SELECT2ND_MAX
            )
        ndisc = int(jnp.sum(parents.blocks >= 0))
        obs.span_event(
            "bfs.result", source=source, levels=int(niter),
            discovered=ndisc,
        )
        obs.gauge("smoke.nnz", int(len(rows_u)))

        # serve-path trace (round 15): every request sampled, pumped
        # deterministically (no worker thread), stages -> JSONL
        from combblas_tpu.obs import trace as obs_trace
        from combblas_tpu.serve import GraphEngine, ServeConfig

        prev_rate = obs_trace.sample_rate()
        obs_trace.set_sample_rate(1.0)
        try:
            engine = GraphEngine.from_coo(
                grid, rows_u, cols_u, n, kinds=("bfs",)
            )
            cfg = ServeConfig(
                lane_widths=(1, 2, 4), update_autostart=False
            )
            with obs.span("smoke.serve"):
                srv = engine.serve(cfg)
                srv.warmup(widths=(1, 2, 4))
                roots = np.flatnonzero(deg > 0)[:5]
                futs = [srv.submit("bfs", int(x)) for x in roots]
                while srv.pump(force=True):
                    pass
                for f in futs:
                    f.result(timeout=60)
                srv.close()
        finally:
            obs_trace.set_sample_rate(prev_rate)
    return obs.dump_jsonl()


def run_stitched(scale: int = 6, edgefactor: int = 4,
                 out_path: str | None = None) -> str:
    """Smallest STITCHED-trace entrypoint (round 18): one subprocess
    replica, one sampled BFS request — the dump carries ONE
    schema-``trace`` record spanning two processes (``route`` ->
    ``ipc_send`` -> ``ipc_wait`` -> the child's queue/assemble/
    execute/scatter marks -> ``ipc_recv``) whose stages sum to the
    request wall, plus the fleet's IPC channel accounting and the
    ``fleetlog/v1`` supervision timeline in the fleet workdir.

        JAX_PLATFORMS=cpu python benchmarks/obs_smoke.py --stitched
    """
    import numpy as np

    from combblas_tpu import obs
    from combblas_tpu.obs import trace as obs_trace
    from combblas_tpu.serve import ProcessFleet, ServeConfig
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    if out_path is None:
        out_path = os.path.join(
            tempfile.gettempdir(), "obs_smoke_stitched.jsonl"
        )
    obs.enable(jsonl_path=out_path, install_hooks=False)
    prev_rate = obs_trace.sample_rate()
    obs_trace.set_sample_rate(1.0)
    work = tempfile.mkdtemp(prefix="obs_smoke_fleet_")
    n = 1 << scale
    rows, cols = rmat_symmetric_coo_host(42, scale, edgefactor)
    fr = ProcessFleet.build(
        (1, 1), rows, cols, n, replicas=1, kinds=("bfs",),
        config=ServeConfig(lane_widths=(1, 2)),
        wal_dir=os.path.join(work, "wal"),
        workdir=os.path.join(work, "proc"),
        hb_interval_s=0.2, hb_timeout_s=10.0,
    )
    try:
        deg = np.bincount(rows, minlength=n)
        root = int(np.flatnonzero(deg > 0)[0])
        fr.submit("bfs", root).result(timeout=120)
        for rec in obs_trace.records():
            if rec["labels"].get("fleet") == "process":
                stages = " -> ".join(
                    s["stage"] for s in rec["stages"]
                )
                print(f"stitched [{stages}] wall_s={rec['wall_s']:.4f}")
        print(f"fleetlog {fr.fleetlog.path}")
    finally:
        fr.close(drain=True)
        obs_trace.set_sample_rate(prev_rate)
    return obs.dump_jsonl()


def run_net(scale: int = 6, edgefactor: int = 4,
            out_path: str | None = None) -> str:
    """Smallest SOCKET-PATH trace entrypoint (round 19): one in-process
    ``Server`` behind a ``NetFrontend`` TCP listener, one sampled BFS
    request through a real ``NetClient`` connection — the dump carries
    a schema-``trace`` record whose stages span the wire
    (``net_accept -> net_read -> queue/assemble/execute ->
    net_write``) and still sum to the request wall.

        JAX_PLATFORMS=cpu python benchmarks/obs_smoke.py --net
    """
    import numpy as np

    from combblas_tpu import obs
    from combblas_tpu.obs import trace as obs_trace
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import (
        GraphEngine,
        NetClient,
        NetFrontend,
        ServeConfig,
    )
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    if out_path is None:
        out_path = os.path.join(
            tempfile.gettempdir(), "obs_smoke_net.jsonl"
        )
    obs.enable(jsonl_path=out_path, install_hooks=False)
    prev_rate = obs_trace.sample_rate()
    obs_trace.set_sample_rate(1.0)
    n = 1 << scale
    rows, cols = rmat_symmetric_coo_host(42, scale, edgefactor)
    engine = GraphEngine.from_coo(
        Grid.make(1, 1), rows, cols, n, kinds=("bfs",)
    )
    srv = engine.serve(
        ServeConfig(lane_widths=(1, 2), update_autostart=False)
    )
    srv.start()
    srv.warmup(widths=(1, 2))
    fe = NetFrontend(srv)
    try:
        deg = np.bincount(rows, minlength=n)
        root = int(np.flatnonzero(deg > 0)[0])
        with NetClient("127.0.0.1", fe.port) as client:
            client.submit("bfs", root, timeout_s=120.0)
        for rec in obs_trace.records():
            if rec["labels"].get("transport") == "net":
                stages = " -> ".join(
                    s["stage"] for s in rec["stages"]
                )
                print(f"net [{stages}] wall_s={rec['wall_s']:.4f}")
    finally:
        fe.close()
        srv.close()
        obs_trace.set_sample_rate(prev_rate)
    return obs.dump_jsonl()


def main():
    flags = {"--stitched": run_stitched, "--net": run_net}
    argv = [a for a in sys.argv[1:] if a not in flags]
    entry = run
    for flag, fn in flags.items():
        if flag in sys.argv[1:]:
            entry = fn
    out = entry(out_path=argv[0] if argv else None)
    from combblas_tpu import obs

    print(f"wrote {out}")
    obs.print_report()
    for rec in obs.metrics_snapshot():
        if rec["kind"] == "counter":
            print(f"  {rec['name']}{rec['labels'] or ''} = {rec['value']}")


if __name__ == "__main__":
    main()
