"""Query-serving benchmark: batched lanes vs one-call-per-query.

    python benchmarks/serve_bench.py            # 8 virtual CPU devices

Measures, on the tier-1 8-virtual-device CPU mesh (2x4 grid), a mixed
BFS/PageRank query stream served two ways over the SAME warm engine:

  * BASELINE — one engine call per query (the warm width-1 plan: no
    compile or trace cost is charged to the baseline; the gap is purely
    the batching, i.e. per-launch overhead and unamortized lanes);
  * BATCHED — the ``serve.Server`` micro-batcher coalescing the stream
    into width-``BENCH_SERVE_WIDTH`` (default 16) lane buckets.

Reports queries/s for both plus per-request p50/p99 latency under the
batched server, and CHECKS the serving acceptance gates:

  * ``speedup`` >= 4x at batch width 16 (the batched-serving payoff);
  * ``retraces_after_warmup`` == 0 — asserted via the engine's
    trace-time counter, mirrored in obs as ``trace.serve``;
  * ``backpressure_ok`` — a full queue REJECTS ``submit()`` with a
    retry-after hint instead of blocking unboundedly.

"ok" in the final JSON line is the AND of the three gates.

BENCH_OBS=1 attaches the structured telemetry sidecar through
``obs.enable_sidecar`` (queue-depth gauge, occupancy/padding-waste and
latency histograms, plan-cache + trace counters land in the JSONL);
``bench.py`` invokes this file under ``BENCH_SERVE=1`` with the sidecar
on by default.

BENCH_SERVE_CHAOS=1 runs the CHAOS scenario instead (ISSUE 6): the same
mixed stream through the threaded server under a seeded
``BENCH_SERVE_CHAOS_RATE`` (default 5%) execute-fault schedule plus a
``BENCH_SERVE_CHAOS_SWAPS``-deep (default 3) graph hot-swap storm, and
gates on: availability >= 95% of well-formed requests, ZERO stranded
futures, zero post-swap retraces (same-shape versions: the plan cache
must survive every swap), and all swaps applied. Reports availability
%, ok-request p50/p99 latency, and per-swap latency.

BENCH_SERVE_MUTATE=1 runs the MIXED READ/WRITE scenario instead
(ISSUE 9): the read stream serves while a writer thread streams
edge-churn batches through ``submit_update`` (the dynamic mutation
lane, docs/dynamic.md), and gates on zero steady-state retraces, all
merges incremental, and the counter-backed rebuild-amortization ratio
(one measured full ``build_version`` / mean incremental merge) > 1.
Reports p99 read latency under writes, merge mode counts, and
rows-patched/rebucketed counters.  ``BENCH_SERVE_MUTATE_WRITES`` sets
the update-batch count (default 24).

BENCH_SERVE_POOL=1 runs the MULTI-TENANT POOL scenario (ISSUE 12):
``BENCH_POOL_TENANTS`` (default 4, the acceptance floor) tenant graphs
behind one ``EnginePool``, three phases —

  * WFQ fairness (deterministic, pump-driven): two saturated tenants
    at weights 3:1 must serve within 25% of their weighted shares;
  * mixed read/write load (threaded pool worker):
    ``BENCH_SERVE_QUERIES`` (default 2000) weighted mixed-kind queries
    across all tenants plus a ``BENCH_POOL_WRITES`` (default 16)
    update stream into tenant t0, reporting throughput, p50/p99
    latency, per-tenant rejects and occupancy/padding waste, gating
    ZERO steady-state retraces across every tenant's plan cache;
  * LRU eviction: the byte budget is tightened to half the resident
    set, tenants are touched round-robin, and the gate asserts
    resident device bytes STAY under the budget at every admit while
    an evicted tenant re-admits BIT-EXACTLY (``to_host_coo``).

Emits the standard ``{summary, metric, value, median, warning, rc}``
final stdout line + BENCH_SUMMARY.json (with a per-tenant breakdown)
itself, so a standalone run honors the bench headline contract;
results are archived under benchmarks/results/r14/.

BENCH_SERVE_RECOVERY=1 runs the DURABILITY/SELF-HEALING scenario
(ISSUE 14): a ``BENCH_FLEET_REPLICAS``-wide (default 3) durable
``FleetRouter`` (write-ahead log + background checkpointer in a temp
dir) serves a mixed read/write stream while replica workers are KILLED
mid-stream — a non-home replica first, then the HOME itself (forcing a
promotion at the WAL's seqno frontier) — with the supervisor healing
continuously.  Gates: availability >= 95% of reads, ZERO acknowledged
writes lost (every acked edge present in the crash-recovered state),
recovered state bit-exact (``recover_version`` vs the surviving home,
``to_host_coo`` equal), and 0 post-recovery retraces across the healed
fleet.  Results under benchmarks/results/r16/.

BENCH_FLEET=process upgrades the recovery scenario to the PROCESS
fleet (round 17, ISSUE 15): replicas are real OS subprocesses
(``serve.ProcessFleet``) and the kills are real ``SIGKILL``s fired
through the scripted ``ProcessFaultPlan`` — a non-home replica first,
then the HOME mid-stream (promotion at the WAL frontier over IPC) —
followed by a ``SIGSTOP`` hang phase: the stopped replica must be
detected by HEARTBEAT TIMEOUT and routed around (reads keep serving)
rather than wedging the router.  Same four gates as the thread
scenario, plus the first honest replica-parallelism measurement:
read-only throughput through N subprocess replicas (own JAX runtimes,
no shared exec lock) vs the SAME stream through the thread fleet's
shared-lock serialization.  Results under benchmarks/results/r17/.

BENCH_SERVE_NET=1 runs the OPEN-LOOP network scenario (round 19) by
delegating to ``combblas_tpu.serve.net.loadgen``: a seeded Poisson
arrival stream over hundreds of TCP connections against a process
fleet, latencies measured from SCHEDULED arrival time.  Every
scenario in THIS file is closed-loop (the next request waits for the
last), so each summary carries ``warning: "closed-loop (coordinated
omission)"`` — do not compare its tail latencies against the
open-loop numbers (results under benchmarks/results/r19/).

BENCH_SERVE_SHARD=1 runs the SHARDED SERVING scenario (round 20): one
graph row-partitioned over ``BENCH_SHARD_SLICES`` (default 2)
subprocess slices (each a rectangular slab on its own JAX runtime),
served as ONE engine through the batcher.  Gates: per-slice device
residency <= 60% of the unsharded build, bfs/sssp bit-exact vs
unsharded (before AND after a slice SIGKILL+respawn), availability
>= 99% through the kill, zero post-warmup retraces across the
respawn, and two-phase writes + whole-service recovery reassembling
the identical global COO.  Results under benchmarks/results/r20/.
"""

from __future__ import annotations

import json
import os
import sys
import time

# tier-1 virtual mesh, set BEFORE jax initializes its backend
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
)

from combblas_tpu.utils import device_fields  # noqa: E402

SCALE = int(os.environ.get("BENCH_SERVE_SCALE", "9"))
EDGEFACTOR = int(os.environ.get("BENCH_SERVE_EDGEFACTOR", "8"))
WIDTH = int(os.environ.get("BENCH_SERVE_WIDTH", "16"))
NQUERIES = int(os.environ.get("BENCH_SERVE_QUERIES", "256"))


def _percentile(xs: list[float], q: float) -> float:
    # ONE percentile implementation repo-wide (round 15): the obs
    # sinks' quantile helper, shared with the registry snapshot, the
    # JSONL aggregate and the Prometheus exporter
    from combblas_tpu.obs.sinks import quantiles

    return quantiles(xs, (q,))[q]


def _restores_trace_rate(fn):
    """Scenario decorator: whatever sampling rate the scenario sets,
    the PROCESS-GLOBAL rate is restored on every exit path (exception
    included) — a later scenario or test in the same process must not
    inherit it."""
    import functools

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        from combblas_tpu.obs import trace as obs_trace

        prev = obs_trace.sample_rate()
        try:
            return fn(*args, **kwargs)
        finally:
            obs_trace.set_sample_rate(prev)

    return wrapper


def _trace_decomposition(obs_trace, records=None) -> dict | None:
    """Per-stage mean latency (ms) from the sampled request traces —
    the summary-JSON latency decomposition (None when nothing was
    sampled).  ``records`` narrows the fold to a subset (the process
    scenario folds only its STITCHED cross-process traces)."""
    summary = obs_trace.stage_summary(records)
    if not summary:
        return None
    return {
        stage: round(1e3 * d["mean_s"], 3)
        for stage, d in summary.items()
    }


#: Stitched-trace stages owned by the router (its own marks) vs the
#: wire (send + the residual the child's marks don't cover); every
#: other stage was measured INSIDE the child and shipped back.
_ROUTER_STAGES = frozenset(("route", "ipc_recv"))
_IPC_STAGES = frozenset(("ipc_send", "ipc_wait"))


def _stitched_split(decomp: dict | None) -> dict | None:
    """Fold a stitched-trace decomposition into the router / ipc /
    child 3-way split — the process fleet's isolation-tax headline."""
    if not decomp:
        return None
    out = {"router_ms": 0.0, "ipc_ms": 0.0, "child_ms": 0.0}
    for stage, ms in decomp.items():
        if stage.startswith("_"):  # summary pseudo-keys (_wall)
            continue
        if stage in _ROUTER_STAGES:
            out["router_ms"] += ms
        elif stage in _IPC_STAGES:
            out["ipc_ms"] += ms
        else:
            out["child_ms"] += ms
    return {k: round(v, 3) for k, v in out.items()}


def _setup(scale, edgefactor, width, nqueries, grid_shape, kinds,
           widths, keep_coo=False):
    """Shared graph/stream/warmup setup: the chaos scenario must
    measure the SAME engine, stream, and warm plans the baseline
    scenario does.  ``keep_coo=True`` retains the host edge list (the
    mutation lane's merge-state bootstrap — the mutate scenario)."""
    import numpy as np

    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import GraphEngine
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    n = 1 << scale
    rows, cols = rmat_symmetric_coo_host(42, scale, edgefactor)
    grid = Grid.make(*grid_shape)

    # raw COO straight in: from_coo deduplicates internally (one
    # int64-key unique pass — doing it here too would double the sort)
    t0 = time.perf_counter()
    engine = GraphEngine.from_coo(
        grid, rows, cols, n, kinds=kinds, keep_coo=keep_coo
    )
    load_s = time.perf_counter() - t0

    # mixed query stream: alternating kinds over random reachable roots
    # (raw rows give the same reachable set as the deduped edge list)
    deg = np.bincount(rows, minlength=n)
    rng = np.random.default_rng(7)
    roots = rng.choice(np.flatnonzero(deg > 0), size=nqueries)
    stream = [
        (kinds[i % len(kinds)], int(r)) for i, r in enumerate(roots)
    ]

    t0 = time.perf_counter()
    engine.warmup(kinds=kinds, widths=widths)
    warmup_s = time.perf_counter() - t0
    return engine, rows, cols, roots, stream, load_s, warmup_s


def run(scale: int = SCALE, edgefactor: int = EDGEFACTOR,
        width: int = WIDTH, nqueries: int = NQUERIES,
        grid_shape=(2, 4), kinds=("bfs", "pagerank")) -> dict:
    import numpy as np

    from combblas_tpu import obs
    from combblas_tpu.serve import BackpressureError, ServeConfig

    sidecar = obs.enable_sidecar("serve")

    # plans for every bucket the server may flush under, plus width-1
    # for the baseline — after this, ZERO traces is the contract
    widths = tuple(sorted({1, width}))
    engine, rows, _cols, roots, stream, load_s, warmup_s = _setup(
        scale, edgefactor, width, nqueries, grid_shape, kinds, widths,
    )
    mark = engine.trace_mark()

    # -- baseline: one warm call per query --------------------------------
    t0 = time.perf_counter()
    for kind, root in stream:
        engine.execute(kind, np.asarray([root], np.int32))
    base_s = time.perf_counter() - t0
    qps_base = nqueries / base_s

    # -- batched serving ---------------------------------------------------
    cfg = ServeConfig(
        lane_widths=(width,),  # the acceptance gate's fixed bucket
        max_queue=max(4 * width, nqueries),
        max_wait_s=0.05,
    )
    lat: list[float] = []

    def _stamp(ts):
        # completion-time stamping: measuring at result()-collection
        # time would charge a fast request for an earlier slow batch
        return lambda _f: lat.append(time.monotonic() - ts)

    t0 = time.perf_counter()
    with engine.serve(cfg) as srv:
        submitted = []
        for kind, root in stream:
            f = srv.submit(kind, root)
            f.add_done_callback(_stamp(time.monotonic()))
            submitted.append(f)
        for f in submitted:
            f.result(timeout=600)
    batch_s = time.perf_counter() - t0
    qps_batch = nqueries / batch_s
    stats = srv.stats()

    retraces = engine.retraces_since(mark)

    # -- backpressure gate: a full queue rejects, never blocks -------------
    tiny = engine.serve(ServeConfig(
        lane_widths=(width,), max_queue=4, max_wait_s=30.0,
    ))  # worker NOT started: the queue cannot drain
    backpressure_ok = False
    retry_after = None
    try:
        for i in range(8):
            tiny.scheduler.submit("bfs", int(roots[0]))
    except BackpressureError as e:
        backpressure_ok = True
        retry_after = e.retry_after_s
    tiny.scheduler.fail_pending(RuntimeError("bench probe teardown"))

    speedup = qps_batch / qps_base if qps_base else float("inf")
    out = {
        "metric": "serve_throughput",
        "warning": "closed-loop (coordinated omission)",
        "unit": "queries/s",
        "value": round(qps_batch, 2),
        "qps_batched": round(qps_batch, 2),
        "qps_baseline": round(qps_base, 2),
        "speedup": round(speedup, 2),
        "p50_ms": round(1e3 * _percentile(lat, 0.50), 2),
        "p99_ms": round(1e3 * _percentile(lat, 0.99), 2),
        "width": width,
        "nqueries": nqueries,
        "kinds": list(kinds),
        "scale": scale,
        "grid": list(grid_shape),
        "edges_raw": int(len(rows)),  # pre-dedup (from_coo dedups)
        "load_s": round(load_s, 2),
        "warmup_s": round(warmup_s, 2),
        "mean_occupancy": stats["mean_occupancy"],
        "batches": stats["batches"],
        "retraces_after_warmup": retraces,
        "backpressure_ok": backpressure_ok,
        "backpressure_retry_after_s": retry_after,
        "ok": bool(
            speedup >= 4.0 and retraces == 0 and backpressure_ok
        ),
    }
    obs.gauge("serve.bench.qps_batched", qps_batch)
    obs.gauge("serve.bench.qps_baseline", qps_base)
    obs.gauge("serve.bench.speedup", speedup)
    if sidecar:
        try:
            out["obs_jsonl"] = obs.dump_jsonl()
        except Exception as e:  # telemetry must never fail the bench
            out["obs_error"] = str(e)
    return out


@_restores_trace_rate
def run_chaos(scale: int = SCALE, edgefactor: int = EDGEFACTOR,
              width: int = WIDTH, nqueries: int | None = None,
              grid_shape=(2, 4), kinds=("bfs", "pagerank")) -> dict:
    """Availability under injected faults + a hot-swap storm (the
    resilience acceptance scenario — see module docstring)."""
    from concurrent.futures import Future, wait

    from combblas_tpu import obs
    from combblas_tpu.serve import BackpressureError, ServeConfig

    sidecar = obs.enable_sidecar("serve-chaos")
    from combblas_tpu.obs import trace as obs_trace

    if sidecar:
        # sampled request traces feed the summary's latency
        # decomposition (deterministic: same rids = same sampled set;
        # rate restored by @_restores_trace_rate on every exit path)
        obs_trace.set_sample_rate(
            float(os.environ.get("BENCH_TRACE_SAMPLE", "0.25"))
        )
    rate = float(os.environ.get("BENCH_SERVE_CHAOS_RATE", "0.05"))
    # default seed 11 fires its first 5% fault on the 4th execute call:
    # even a short, well-coalesced stream provably exercises recovery
    seed = int(os.environ.get("BENCH_SERVE_CHAOS_SEED", "11"))
    nswaps = int(os.environ.get("BENCH_SERVE_CHAOS_SWAPS", "3"))
    nqueries = (
        int(os.environ.get("BENCH_SERVE_QUERIES", "400"))
        if nqueries is None else nqueries
    )

    # a generous deadline SLO so the budget-burn surface is live under
    # chaos: injected faults and their poisons burn the error budget
    slo_deadline_s = float(
        os.environ.get("BENCH_SERVE_SLO_DEADLINE_S", "30")
    )
    widths = tuple(sorted({1, 2, 4, 8, width}))
    engine, rows, cols, _roots, stream, _load_s, _warmup_s = _setup(
        scale, edgefactor, width, nqueries, grid_shape, kinds, widths,
    )
    # the swap storm's versions: SAME COO, so operand shapes match and
    # the zero-post-swap-retrace gate is a real plan-cache assertion
    t0 = time.perf_counter()
    versions = [engine.build_version(rows, cols) for _ in range(nswaps)]
    build_s = time.perf_counter() - t0
    mark = engine.trace_mark()

    cfg = ServeConfig(
        lane_widths=widths, max_queue=max(4 * width, nqueries),
        max_wait_s=0.005, slo_deadline_s=slo_deadline_s,
        slo_target=0.95,
    )
    lat_of: dict = {}  # future -> completion latency (ok OR failed)

    def _stamp(fut, ts):
        fut.add_done_callback(
            lambda f: lat_of.__setitem__(f, time.monotonic() - ts)
        )

    swap_s: list[float] = []
    swap_at = {
        (k + 1) * nqueries // (nswaps + 1): k for k in range(nswaps)
    }
    t0 = time.perf_counter()
    futs = []
    with engine.serve(cfg) as srv:
        srv.faults.rate("engine.execute", rate, seed=seed)
        for i, (kind, root) in enumerate(stream):
            try:
                f = srv.submit(kind, root)
                _stamp(f, time.monotonic())
            except BackpressureError as e:
                # breaker fast-fail / queue-full under high chaos
                # rates: unavailability is DATA here, not a crash
                f = Future()
                f.set_exception(e)
            futs.append(f)
            k = swap_at.get(i)
            if k is not None:  # mid-stream, under live load
                swap_s.append(srv.swap_graph(versions[k])["swap_s"])
        wait(futs, timeout=600)  # failures are data; stranded counted
        stats = srv.stats()
        fault_stats = srv.faults.stats()
    wall_s = time.perf_counter() - t0

    stranded = sum(1 for f in futs if not f.done())
    ok = sum(
        1 for f in futs if f.done() and f.exception(timeout=0) is None
    )
    availability = ok / nqueries
    retraces = engine.retraces_since(mark)
    lat = [lat_of[f] for f in futs if f in lat_of]
    ok_lat = [
        lat_of[f] for f in futs
        if f in lat_of and f.done() and f.exception(timeout=0) is None
    ]
    per_kind = stats["per_kind"]

    out = {
        "metric": "serve_chaos_availability",
        "warning": "closed-loop (coordinated omission)",
        "unit": "fraction_ok",
        "value": round(availability, 4),
        "availability_pct": round(100 * availability, 2),
        "ok": bool(
            availability >= 0.95
            and stranded == 0
            and retraces == 0
            and len(swap_s) == nswaps
        ),
        "nqueries": nqueries,
        "completed_ok": ok,
        "stranded": stranded,
        "fault_rate": rate,
        "fault_seed": seed,
        "faults_injected": fault_stats["fired"].get("engine.execute", 0),
        "retried": {
            k: per_kind[k]["retried"] for k in per_kind
        },
        "poisoned": {
            k: per_kind[k]["poisoned"] for k in per_kind
        },
        "breaker_opened": {
            k: per_kind[k].get("breaker", {}).get("opened_total", 0)
            for k in per_kind
        },
        "p50_ms": round(1e3 * _percentile(lat, 0.50), 2) if lat else None,
        "p99_ms": round(1e3 * _percentile(lat, 0.99), 2) if lat else None,
        "p99_ok_ms": (
            round(1e3 * _percentile(ok_lat, 0.99), 2) if ok_lat else None
        ),
        "swaps": len(swap_s),
        "swap_latency_ms": [round(1e3 * s, 3) for s in swap_s],
        "swap_build_s": round(build_s, 2),
        "retraces_after_swaps": retraces,
        "qps_under_chaos": round(nqueries / wall_s, 2),
        "width": width,
        "scale": scale,
        "grid": list(grid_shape),
        "kinds": list(kinds),
        "batches": stats["batches"],
        "graph_version": stats["graph_version"],
        # round 15: sampled-trace latency decomposition + the SLO
        # error budget's view of the chaos (burn counts the injected
        # damage the availability gate tolerates)
        "latency_decomposition_ms": _trace_decomposition(obs_trace),
        "slo": stats.get("slo"),
        "flightrec": stats.get("flightrec"),
    }
    obs.gauge("serve.bench.chaos_availability", availability)
    if sidecar:
        try:
            out["obs_jsonl"] = obs.dump_jsonl()
        except Exception as e:  # telemetry must never fail the bench
            out["obs_error"] = str(e)
    return out


def run_mutate(scale: int = SCALE, edgefactor: int = EDGEFACTOR,
               width: int = WIDTH, nqueries: int | None = None,
               grid_shape=(2, 4), kinds=("bfs", "pagerank")) -> dict:
    """BENCH_SERVE_MUTATE=1 — mixed read/write traffic (ISSUE 9): the
    usual read stream through the threaded server WHILE a writer thread
    streams edge-churn updates into ``submit_update``.  Measures p99
    read latency under the mix and the rebuild-amortization counters,
    and gates on:

      * zero steady-state retraces (incremental merges preserve every
        operand shape, so same-shape swaps keep the warm plans);
      * >= 1 update merged, ALL incrementally (the writer churns edges
        whose endpoints' degree classes have slack, the in-place path);
      * incremental merge measurably cheaper than a full rebuild at
        this delta fraction: ``amortization`` = (one measured full
        ``build_version``) / (mean incremental merge latency) > 1,
        counter-backed from ``stats()['updates']``.
    """
    import threading

    import numpy as np

    from combblas_tpu import obs
    from combblas_tpu.serve import BackpressureError, ServeConfig

    sidecar = obs.enable_sidecar("serve-mutate")
    nqueries = (
        int(os.environ.get("BENCH_SERVE_QUERIES", "256"))
        if nqueries is None else nqueries
    )
    nwrites = int(os.environ.get("BENCH_SERVE_MUTATE_WRITES", "24"))

    widths = tuple(sorted({1, 2, 4, 8, width}))
    engine, rows, cols, _roots, stream, load_s, warmup_s = _setup(
        scale, edgefactor, width, nqueries, grid_shape, kinds, widths,
        keep_coo=True,
    )
    n = engine.nrows
    r0, c0, _ = engine.version.host_coo
    deg = np.asarray(engine.version.deg)

    # rebuild baseline: one full from_coo-pipeline build of the SAME
    # edge list — what every write batch would cost without the
    # incremental merge (measured, not modeled)
    t0 = time.perf_counter()
    engine.build_version(rows, cols)
    rebuild_s = time.perf_counter() - t0

    # churn pairs whose endpoint degrees sit below their fine-ladder
    # class width (+1 stays in class): provably the in-place path.
    # DISJOINT pairs (each vertex in at most one) so no endpoint's
    # degree drifts across batches out of its slack class — and O(pool)
    # instead of materializing the O(pool^2) cross product
    slack = np.isin(deg, (5, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19))
    present = set(zip(r0.tolist(), c0.tolist()))
    pool = np.flatnonzero(slack).tolist()
    pairs = []
    for a, b in zip(pool[0::2], pool[1::2]):
        if (a, b) not in present:
            pairs.append((a, b))
        if len(pairs) >= max(nwrites, 1):
            break

    cfg = ServeConfig(
        lane_widths=widths, max_queue=max(4 * width, nqueries),
        max_wait_s=0.005, update_flush=4, update_max_delay_s=0.01,
    )
    lat_of: dict = {}
    mark = engine.trace_mark()
    write_futs = []
    write_rejects = 0

    t0 = time.perf_counter()
    with engine.serve(cfg) as srv:

        def writer():
            nonlocal write_rejects
            # insert each slack pair, then delete it one batch later:
            # real structural change per merge, degree classes stable
            for k, (a, b) in enumerate(pairs + pairs):
                op = "insert" if k < len(pairs) else "delete"
                try:
                    write_futs.append(srv.submit_update(
                        [(op, a, b), (op, b, a)]
                    ))
                except BackpressureError:
                    write_rejects += 1
                time.sleep(0.001)

        wt = threading.Thread(target=writer)
        wt.start()
        futs = []
        for kind, root in stream:
            ts = time.monotonic()
            try:
                f = srv.submit(kind, root)
            except BackpressureError:
                continue
            f.add_done_callback(
                lambda _f, ts=ts: lat_of.setdefault(
                    _f, time.monotonic() - ts
                )
            )
            futs.append(f)
        for f in futs:
            f.result(timeout=600)
        wt.join(60)
        for f in write_futs:
            f.result(timeout=600)
        stats = srv.stats()
    wall_s = time.perf_counter() - t0

    retraces = engine.retraces_since(mark)
    upd = stats["updates"]
    incr = upd["by_mode"].get("incremental", 0)
    rebuilds = upd["by_mode"].get("rebuild", 0)
    incr_s = upd["merge_s_by_mode"].get("incremental", 0.0)
    mean_incr_s = incr_s / incr if incr else None
    amortization = (
        rebuild_s / mean_incr_s if mean_incr_s else None
    )
    lat = [lat_of[f] for f in futs if f in lat_of]
    ok = bool(
        retraces == 0
        and upd["merges"] >= 1
        and incr >= 1
        and rebuilds == 0
        and amortization is not None
        and amortization > 1.0
    )
    out = {
        "metric": "serve_mutate_amortization",
        "warning": "closed-loop (coordinated omission)",
        "unit": "rebuild_over_incremental",
        "value": round(amortization, 2) if amortization else None,
        "ok": ok,
        "nqueries": len(futs),
        "p50_read_ms": (
            round(1e3 * _percentile(lat, 0.50), 2) if lat else None
        ),
        "p99_read_ms": (
            round(1e3 * _percentile(lat, 0.99), 2) if lat else None
        ),
        "qps_under_writes": round(len(futs) / wall_s, 2),
        "updates_submitted": upd["submitted"],
        "update_merges": upd["merges"],
        "merges_incremental": incr,
        "merges_rebuild": rebuilds,
        "mean_incremental_merge_ms": (
            round(1e3 * mean_incr_s, 3) if mean_incr_s else None
        ),
        "full_rebuild_ms": round(1e3 * rebuild_s, 3),
        "write_rejects": write_rejects,
        "retraces_after_warmup": retraces,
        "graph_version": stats["graph_version"],
        "rows_patched": (
            obs.registry.get_counter("dynamic.merge.rows_patched")
            if obs.ENABLED else None
        ),
        "rows_rebucketed": (
            obs.registry.get_counter("dynamic.merge.rows_rebucketed")
            if obs.ENABLED else None
        ),
        "width": width,
        "scale": scale,
        "grid": list(grid_shape),
        "kinds": list(kinds),
        "load_s": round(load_s, 2),
        "warmup_s": round(warmup_s, 2),
    }
    obs.gauge("serve.bench.mutate_amortization", amortization or 0.0)
    if sidecar:
        try:
            out["obs_jsonl"] = obs.dump_jsonl()
        except Exception as e:  # telemetry must never fail the bench
            out["obs_error"] = str(e)
    return out


@_restores_trace_rate
def run_pool(scale: int = SCALE, edgefactor: int = EDGEFACTOR,
             grid_shape=(2, 4), kinds=("bfs", "pagerank")) -> dict:
    """BENCH_SERVE_POOL=1 — the multi-tenant pool scenario (ISSUE 12);
    see the module docstring for the three phases and their gates."""
    import threading

    import numpy as np

    from combblas_tpu import obs
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import (
        BackpressureError, EnginePool, ServeConfig,
    )
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    sidecar = obs.enable_sidecar("serve-pool")
    from combblas_tpu.obs import trace as obs_trace

    if sidecar:  # rate restored by @_restores_trace_rate
        obs_trace.set_sample_rate(
            float(os.environ.get("BENCH_TRACE_SAMPLE", "0.25"))
        )
    ntenants = max(int(os.environ.get("BENCH_POOL_TENANTS", "4")), 2)
    nqueries = int(os.environ.get("BENCH_SERVE_QUERIES", "2000"))
    nwrites = int(os.environ.get("BENCH_POOL_WRITES", "16"))
    widths = (1, 2, 4, 8, 16)
    n = 1 << scale
    grid = Grid.make(*grid_shape)

    # tenants: independent graphs, weighted 3:1 for the first pair
    # (the fairness phase's A/B), everyone else 1.0
    weights = [3.0, 1.0] + [1.0] * (ntenants - 2)
    cfg = ServeConfig(
        lane_widths=widths, max_queue=4096, max_wait_s=0.005,
        update_flush=4, update_max_delay_s=0.01,
        update_autostart=False,  # the POOL worker merges (WFQ-charged)
        # a generous per-tenant deadline SLO: the budget-burn column
        # in the per-tenant breakdown is live without changing what
        # the scenario admits (a standing backlog stays well inside)
        slo_deadline_s=float(
            os.environ.get("BENCH_SERVE_SLO_DEADLINE_S", "120")
        ),
        slo_target=0.95,
    )
    pool = EnginePool(grid)
    t0 = time.perf_counter()
    tenant_rows = {}
    for i in range(ntenants):
        rows, cols = rmat_symmetric_coo_host(42 + i, scale, edgefactor)
        name = f"t{i}"
        tenant_rows[name] = rows
        pool.add_tenant(
            name, rows, cols, n, weight=weights[i], config=cfg,
            kinds=kinds, keep_coo=(i == 0),
        )
    load_s = time.perf_counter() - t0
    names = [f"t{i}" for i in range(ntenants)]

    psrv = pool.serve()
    t0 = time.perf_counter()
    psrv.warmup()  # every tenant, every (kind, width) lane bucket
    warmup_s = time.perf_counter() - t0
    marks = {t: pool.engine(t).trace_mark() for t in names}

    # -- phase 1: WFQ weighted share (deterministic, pump-driven) ----------
    for _ in range(120):
        psrv.submit("t0", "bfs", 1)
        psrv.submit("t1", "bfs", 1)
    served0 = dict(psrv.wfq.describe()["served"])
    for _ in range(3):  # three DRR rounds, both queues stay saturated
        psrv.pump(force=True)
    served1 = psrv.wfq.describe()["served"]
    share = {
        t: served1.get(t, 0) - served0.get(t, 0) for t in ("t0", "t1")
    }
    fair_ratio = share["t0"] / max(share["t1"], 1)
    fairness_ok = 0.75 * 3.0 <= fair_ratio <= 1.25 * 3.0
    while psrv.pump(force=True):  # drain the saturation backlog
        pass

    # -- phase 2: mixed read/write load under the threaded worker ----------
    rng = np.random.default_rng(7)
    p = np.asarray(weights) / sum(weights)
    roots_of = {}
    for t in names:
        deg = np.bincount(tenant_rows[t], minlength=n)
        roots_of[t] = np.flatnonzero(deg > 0)
    stream = [
        (
            names[int(rng.choice(ntenants, p=p))],
            kinds[q % len(kinds)],
        )
        for q in range(nqueries)
    ]
    # churn pairs whose endpoint degrees sit in slack ladder classes
    # (the run_mutate recipe): provably in-place merges, so the
    # zero-retrace gate is a real plan-cache assertion under writes
    deg0 = np.asarray(pool.engine("t0").version.deg)
    slack = np.isin(deg0, (5, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19))
    pool_v = np.flatnonzero(slack).tolist()
    r0, c0, _ = pool.engine("t0").version.host_coo
    present = set(zip(r0.tolist(), c0.tolist()))
    pairs = []
    for a, b in zip(pool_v[0::2], pool_v[1::2]):
        if (a, b) not in present:
            pairs.append((a, b))
        if len(pairs) >= max(nwrites, 1):
            break

    lat_of: dict = {}
    rejects = {t: 0 for t in names}
    write_futs = []
    write_rejects = 0
    t0 = time.perf_counter()
    with psrv:

        def writer():
            nonlocal write_rejects
            for k, (a, b) in enumerate(pairs + pairs):
                op = "insert" if k < len(pairs) else "delete"
                try:
                    write_futs.append(psrv.submit_update(
                        "t0", [(op, a, b), (op, b, a)]
                    ))
                except BackpressureError:
                    write_rejects += 1
                time.sleep(0.002)

        wt = threading.Thread(target=writer)
        wt.start()
        futs = []
        for tenant, kind in stream:
            root = int(rng.choice(roots_of[tenant]))
            ts = time.monotonic()
            try:
                f = psrv.submit(tenant, kind, root)
            except BackpressureError:
                rejects[tenant] += 1
                continue
            f.add_done_callback(
                lambda _f, ts=ts, t=tenant: lat_of.setdefault(
                    _f, (t, time.monotonic() - ts)
                )
            )
            futs.append(f)
        wt.join(120)
        # wait(), not result(): a failed/expired request must be
        # COUNTED, not crash the scenario before the summary line —
        # and the stranded gate is only real when futures may still
        # be pending at the check
        from concurrent.futures import wait as _wait

        _wait(futs + write_futs, timeout=600)
        stats = psrv.stats()
    wall_s = time.perf_counter() - t0
    stranded = sum(
        1 for f in futs + write_futs if not f.done()
    )
    read_errors = sum(
        1 for f in futs
        if f.done() and f.exception(timeout=0) is not None
    )
    write_errors = sum(
        1 for f in write_futs
        if f.done() and f.exception(timeout=0) is not None
    )
    retraces = {
        t: pool.engine(t).retraces_since(marks[t]) for t in names
    }
    lat_by_t = {t: [] for t in names}
    for t, dt in lat_of.values():
        lat_by_t[t].append(dt)
    lat_all = [dt for _t, dt in lat_of.values()]
    merges = stats["servers"]["t0"]["updates"]["merges"]

    # -- phase 3: LRU eviction under a tightened byte budget ---------------
    sizes = {
        t: pool.stats()["tenants"][t]["device_bytes"] for t in names
    }
    before_t1 = pool.engine("t1").version.E.to_host_coo()
    pool.byte_budget = max(sum(sizes.values()) // 2, max(sizes.values()))
    pool.refresh_bytes(names[-1])
    under_budget = [pool.resident_bytes() <= pool.byte_budget]
    for t in names:  # round-robin touches force evict/re-admit churn
        pool.engine(t)
        under_budget.append(
            pool.resident_bytes() <= pool.byte_budget
        )
    after_t1 = pool.engine("t1").version.E.to_host_coo()
    bit_exact = all(
        np.array_equal(x, y) for x, y in zip(before_t1, after_t1)
    )
    pst = pool.stats()
    evictions = {
        t: pst["tenants"][t]["evictions"] for t in names
    }
    under_budget_ok = all(under_budget)

    qps = len(futs) / wall_s if wall_s else 0.0
    per_tenant = {
        t: {
            "weight": weights[i],
            "queries": len(lat_by_t[t]),
            "rejected": rejects[t],
            "p99_ms": (
                round(1e3 * _percentile(lat_by_t[t], 0.99), 2)
                if lat_by_t[t] else None
            ),
            "mean_occupancy": stats["servers"][t].get("mean_occupancy"),
            "retraces": retraces[t],
            "evictions": evictions[t],
            "admits": pst["tenants"][t]["admits"],
            "device_bytes": sizes[t],
            # round 15: the tenant's SLO error-budget burn over the
            # run's window (None when the server stats predate it)
            "slo_burn": (
                (stats["servers"][t].get("slo") or {}).get("burn")
            ),
        }
        for i, t in enumerate(names)
    }
    padding_waste = None
    if obs.ENABLED:
        h = [
            obs.registry.get_histogram(
                "serve.batch.padding_waste", kind=k
            )
            for k in kinds
        ]
        tot = sum(x["count"] for x in h if x)
        if tot:
            padding_waste = round(
                sum(x["sum"] for x in h if x) / tot, 3
            )
    ok = bool(
        sum(retraces.values()) == 0
        and fairness_ok
        and under_budget_ok
        and bit_exact
        and stranded == 0
        and read_errors == 0  # the stream is well-formed, no faults
        and write_errors == 0
        and merges >= 1
        and sum(evictions.values()) >= 1
    )
    out = {
        "metric": "serve_pool_throughput",
        "warning": "closed-loop (coordinated omission)",
        "unit": "queries/s",
        "value": round(qps, 2),
        "ok": ok,
        "tenants": ntenants,
        "nqueries": len(futs),
        "p50_ms": (
            round(1e3 * _percentile(lat_all, 0.50), 2)
            if lat_all else None
        ),
        "p99_ms": (
            round(1e3 * _percentile(lat_all, 0.99), 2)
            if lat_all else None
        ),
        "padding_waste_mean_lanes": padding_waste,
        "retraces_after_warmup": sum(retraces.values()),
        "fair_share_ratio": round(fair_ratio, 2),
        "fairness_ok": fairness_ok,
        "wfq_shares_measured": share,
        "update_merges": merges,
        "write_rejects": write_rejects,
        "stranded": stranded,
        "read_errors": read_errors,
        "write_errors": write_errors,
        "byte_budget": pool.byte_budget,
        "resident_bytes_final": pool.resident_bytes(),
        "under_budget_ok": under_budget_ok,
        "readmit_bit_exact": bit_exact,
        "per_tenant": per_tenant,
        "latency_decomposition_ms": _trace_decomposition(obs_trace),
        "slo_burn_worst": max(
            (v["slo_burn"] for v in per_tenant.values()
             if v["slo_burn"] is not None),
            default=None,
        ),
        "scale": scale,
        "grid": list(grid_shape),
        "kinds": list(kinds),
        "load_s": round(load_s, 2),
        "warmup_s": round(warmup_s, 2),
        "wall_s": round(wall_s, 2),
    }
    obs.gauge("serve.bench.pool_qps", qps)
    if sidecar:
        try:
            out["obs_jsonl"] = obs.dump_jsonl()
        except Exception as e:  # telemetry must never fail the bench
            out["obs_error"] = str(e)
    return out


def run_recovery(scale: int = SCALE, edgefactor: int = EDGEFACTOR,
                 grid_shape=(2, 4), kinds=("bfs", "pagerank")) -> dict:
    """BENCH_SERVE_RECOVERY=1 — replica kills (home included)
    mid-stream under mixed read/write load, healed live by the
    supervisor; see the module docstring for the four gates."""
    import tempfile
    import threading

    import numpy as np

    from combblas_tpu import obs
    from combblas_tpu.dynamic import open_wal, recover_version
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import FleetRouter, ServeConfig

    sidecar = obs.enable_sidecar("serve-recovery")
    nreplicas = max(int(os.environ.get("BENCH_FLEET_REPLICAS", "3")), 2)
    nqueries = int(os.environ.get("BENCH_SERVE_QUERIES", "400"))
    nwrites = int(os.environ.get("BENCH_RECOVERY_WRITES", "24"))
    wal_dir = tempfile.mkdtemp(prefix="combblas-recovery-wal-")

    n = 1 << scale
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    rows, cols = rmat_symmetric_coo_host(42, scale, edgefactor)
    grid = Grid.make(*grid_shape)
    deg = np.bincount(rows, minlength=n)
    rng = np.random.default_rng(7)
    roots = rng.choice(np.flatnonzero(deg > 0), size=nqueries)
    stream = [
        (kinds[i % len(kinds)], int(r)) for i, r in enumerate(roots)
    ]
    # churn pairs absent from the graph (insert-only writes keep the
    # acked-edge-survives check exact)
    present = set(zip(rows.tolist(), cols.tolist()))
    pool = rng.permutation(n).tolist()
    pairs = []
    for a, b in zip(pool[0::2], pool[1::2]):
        if a != b and (a, b) not in present and (b, a) not in present:
            pairs.append((int(a), int(b)))
        if len(pairs) >= nwrites:
            break

    cfg = ServeConfig(
        lane_widths=(1, 2, 4, 8, 16),
        max_queue=max(64, nqueries), max_wait_s=0.005,
        update_flush=2, update_max_delay_s=0.01,
    )
    t0 = time.perf_counter()
    fr = FleetRouter.build(
        grid, rows, cols, n, replicas=nreplicas, config=cfg,
        kinds=kinds, wal_dir=wal_dir,
    )
    load_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fr.warmup()
    warmup_s = time.perf_counter() - t0
    fr.start_supervisor(interval_s=0.02)

    acked: list = []
    write_failures = 0

    def writer():
        nonlocal write_failures
        for a, b in pairs:
            try:
                fr.submit_update(
                    [("insert", a, b), ("insert", b, a)]
                ).result(timeout=120)
                acked.append((a, b))
            except Exception:
                # a write rejected / failed at a kill boundary was
                # never CONFIRMED merged: it may still be durable
                # (WAL-appended) — allowed, but not counted acked
                write_failures += 1
            time.sleep(0.002)

    def kill(i):
        fr.replicas[i].faults.script("replica.death", at=(0,))
        try:
            fr.replicas[i].submit("bfs", int(roots[0]))
        except Exception:
            pass

    kills = {
        nqueries // 3: lambda: kill((fr.home + 1) % nreplicas),
        (2 * nqueries) // 3: lambda: kill(fr.home),  # THE promotion
    }
    ok = failed = 0
    lat: list[float] = []
    t0 = time.perf_counter()
    wt = threading.Thread(target=writer)
    wt.start()
    for i, (kind, root) in enumerate(stream):
        k = kills.get(i)
        if k is not None:
            k()
        ts = time.monotonic()
        try:
            fr.submit(kind, root).result(timeout=120)
            lat.append(time.monotonic() - ts)
            ok += 1
        except Exception:
            failed += 1
    wt.join(300)
    wall_s = time.perf_counter() - t0
    # let the supervisor finish healing the last kill: a quarantined
    # slot is no longer _dead() but stays in _needs_rebuild until its
    # replacement is actually re-admitted
    deadline = time.monotonic() + 30
    while (
        fr._needs_rebuild
        or any(fr._dead(i) for i in range(nreplicas))
    ) and time.monotonic() < deadline:
        time.sleep(0.02)
    availability = ok / nqueries

    # -- gate: 0 post-recovery retraces across the healed fleet ----------
    marks = [s.engine.trace_mark() for s in fr.replicas]
    for kind in kinds:
        for srv in fr.replicas:
            if srv.is_serving():
                srv.submit(kind, int(roots[0])).result(timeout=120)
    post_retraces = sum(
        s.engine.retraces_since(m) for s, m in zip(fr.replicas, marks)
    )
    home_version = fr.replicas[fr.home].engine.version
    stats = fr.stats()
    fr.close(drain=True)

    # -- gates: recovery bit-exact + zero acknowledged-write loss --------
    wal = open_wal(wal_dir)
    recovered = recover_version(wal_dir, wal, grid, kinds=kinds)
    wal.close()
    hr, hc, hv = home_version.E.to_host_coo()
    rr, rc_, rv = recovered.E.to_host_coo()
    bit_exact = (
        np.array_equal(hr, rr) and np.array_equal(hc, rc_)
        and np.array_equal(hv, rv)
    )
    have = set(zip(rr.tolist(), rc_.tolist()))
    lost = [
        p for p in acked
        if p not in have or (p[1], p[0]) not in have
    ]

    out = {
        "metric": "serve_recovery_availability",
        "warning": "closed-loop (coordinated omission)",
        "unit": "fraction_ok",
        "value": round(availability, 4),
        "availability_pct": round(100 * availability, 2),
        "ok": bool(
            availability >= 0.95
            and not lost
            and bit_exact
            and post_retraces == 0
            and stats["promotions"] >= 1
            and stats["replacements"] >= 2  # both kills healed
        ),
        "nqueries": nqueries,
        "reads_ok": ok,
        "reads_failed": failed,
        "read_retries": stats["read_retries"],
        "writes_acked": len(acked),
        "write_failures": write_failures,
        "acked_writes_lost": len(lost),
        "recovered_bit_exact": bit_exact,
        "post_recovery_retraces": post_retraces,
        "promotions": stats["promotions"],
        "replacements": stats["replacements"],
        "final_home": stats["home"],
        "p50_ms": round(1e3 * _percentile(lat, 0.50), 2) if lat else None,
        "p99_ms": round(1e3 * _percentile(lat, 0.99), 2) if lat else None,
        "qps_under_kills": round(nqueries / wall_s, 2),
        "recovered_nnz": int(len(rr)),
        "replicas": nreplicas,
        "scale": scale,
        "grid": list(grid_shape),
        "kinds": list(kinds),
        "load_s": round(load_s, 2),
        "warmup_s": round(warmup_s, 2),
        "wal_dir": wal_dir,
    }
    obs.gauge("serve.bench.recovery_availability", availability)
    if sidecar:
        try:
            out["obs_jsonl"] = obs.dump_jsonl()
        except Exception as e:  # telemetry must never fail the bench
            out["obs_error"] = str(e)
    return out


def _read_burst_qps(router, stream, timeout=120.0) -> float:
    """Read-only throughput through a fleet front door: submit the
    whole stream, wait for every future — wall-clock covers admission
    through settle (the replica-parallelism measurement's probe)."""
    t0 = time.perf_counter()
    futs = [router.submit(kind, root) for kind, root in stream]
    for f in futs:
        f.result(timeout=timeout)
    return len(futs) / (time.perf_counter() - t0)


@_restores_trace_rate
def run_recovery_process(scale: int = SCALE,
                         edgefactor: int = EDGEFACTOR,
                         kinds=("bfs", "pagerank")) -> dict:
    """BENCH_SERVE_RECOVERY=1 BENCH_FLEET=process — the kill-storm
    over REAL crash domains (module docstring): scripted SIGKILLs
    (non-home, then the home mid-stream), a SIGSTOP hang phase, and
    the N-process vs thread-fleet read-throughput comparison."""
    import signal
    import tempfile
    import threading

    import numpy as np

    from combblas_tpu import obs
    from combblas_tpu.dynamic import open_wal, recover_version
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import (
        FleetRouter,
        ProcessFleet,
        ServeConfig,
    )
    from combblas_tpu.utils import checkpoint

    sidecar = obs.enable_sidecar("serve-recovery-process")
    from combblas_tpu.obs import trace as obs_trace

    if sidecar:
        # sampled requests stitch router+IPC+child marks into one
        # trace per request; the summary folds them into the
        # router/ipc/child latency split (rate restored by
        # @_restores_trace_rate on every exit path)
        obs_trace.set_sample_rate(
            float(os.environ.get("BENCH_TRACE_SAMPLE", "0.25"))
        )
    nreplicas = max(int(os.environ.get("BENCH_FLEET_REPLICAS", "3")), 2)
    nqueries = int(os.environ.get("BENCH_SERVE_QUERIES", "400"))
    nwrites = int(os.environ.get("BENCH_RECOVERY_WRITES", "24"))
    nburst = int(os.environ.get("BENCH_PROC_BURST", "200"))
    work = tempfile.mkdtemp(prefix="combblas-procfleet-")
    wal_dir = os.path.join(work, "wal")

    n = 1 << scale
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    rows, cols = rmat_symmetric_coo_host(42, scale, edgefactor)
    # per-replica 1x1 mesh: each subprocess owns its whole runtime,
    # and the thread-fleet comparator shares ONE 1x1 grid — the
    # difference under the burst is exactly the shared exec lock
    grid = Grid.make(1, 1)
    deg = np.bincount(rows, minlength=n)
    rng = np.random.default_rng(7)
    roots = rng.choice(np.flatnonzero(deg > 0), size=nqueries)
    stream = [
        (kinds[i % len(kinds)], int(r)) for i, r in enumerate(roots)
    ]
    burst = [("bfs", int(r)) for r in roots[:nburst]]
    present = set(zip(rows.tolist(), cols.tolist()))
    pool = rng.permutation(n).tolist()
    pairs = []
    for a, b in zip(pool[0::2], pool[1::2]):
        if a != b and (a, b) not in present and (b, a) not in present:
            pairs.append((int(a), int(b)))
        if len(pairs) >= nwrites:
            break

    cfg = ServeConfig(
        lane_widths=(1, 2, 4, 8, 16),
        max_queue=max(64, nqueries), max_wait_s=0.005,
        update_flush=2, update_max_delay_s=0.01,
    )

    # -- comparator: the SAME burst through the thread fleet's
    #    shared-lock serialization (no WAL: read-only probe)
    tfr = FleetRouter.build(
        grid, rows, cols, n, replicas=nreplicas, config=cfg,
        kinds=kinds,
    )
    tfr.warmup()
    thread_qps = _read_burst_qps(tfr, burst)
    tfr.close(drain=False)

    t0 = time.perf_counter()
    fr = ProcessFleet.build(
        (1, 1), rows, cols, n, replicas=nreplicas, config=cfg,
        kinds=kinds, wal_dir=wal_dir,
        workdir=os.path.join(work, "proc"),
        hb_interval_s=0.1, hb_timeout_s=2.0,
        from_coo_kw={"headroom": 0.5},
    )
    load_s = time.perf_counter() - t0
    proc_qps = _read_burst_qps(fr, burst)
    fr.start_supervisor(interval_s=0.02)

    acked: list = []
    write_failures = 0

    def writer():
        nonlocal write_failures
        for a, b in pairs:
            try:
                fr.submit_update(
                    [("insert", a, b), ("insert", b, a)]
                ).result(timeout=120)
                acked.append((a, b))
            except Exception:
                # a write rejected / failed at a kill boundary was
                # never CONFIRMED merged: it may still be durable
                # (WAL-appended) — allowed, but not counted acked
                write_failures += 1
            time.sleep(0.002)

    # scripted REAL signals at routed-submit indices: a non-home
    # SIGKILL first, then the home ("home" resolves at fire time —
    # the promotion scenario)
    fr.proc_faults.sigkill(nqueries // 3,
                           replica=(fr.home + 1) % nreplicas)
    fr.proc_faults.sigkill((2 * nqueries) // 3, replica="home")

    ok = failed = 0
    lat: list[float] = []
    t0 = time.perf_counter()
    wt = threading.Thread(target=writer)
    wt.start()
    for kind, root in stream:
        ts = time.monotonic()
        try:
            fr.submit(kind, root).result(timeout=120)
            lat.append(time.monotonic() - ts)
            ok += 1
        except Exception:
            failed += 1
    wt.join(300)
    wall_s = time.perf_counter() - t0
    deadline = time.monotonic() + 60
    while (
        fr._needs_rebuild
        or any(fr._dead(i) for i in range(nreplicas))
    ) and time.monotonic() < deadline:
        time.sleep(0.02)
    availability = ok / nqueries

    # -- SIGSTOP hang phase: alive-but-silent must be DETECTED by
    #    heartbeat timeout and routed around, never wedging the router
    victim = (fr.home + 1) % nreplicas
    os.kill(fr.replicas[victim].proc.pid, signal.SIGSTOP)
    stop_ok = 0
    t_stop = time.monotonic()
    detected_s = None
    while time.monotonic() - t_stop < 30:
        try:
            fr.submit("bfs", int(roots[0])).result(timeout=120)
            stop_ok += 1
        except Exception:
            pass
        if detected_s is None and fr.replicas[victim].quarantined:
            detected_s = time.monotonic() - t_stop
        if detected_s is not None:
            break
        time.sleep(0.05)
    sigstop_detected = detected_s is not None
    deadline = time.monotonic() + 60
    while (
        fr._needs_rebuild
        or any(fr._dead(i) for i in range(nreplicas))
    ) and time.monotonic() < deadline:
        time.sleep(0.02)

    # -- gate: 0 post-recovery retraces across the healed fleet ----------
    marks = fr.trace_marks()
    for kind in kinds:
        for i, rp in enumerate(fr.replicas):
            if rp.is_serving():
                rp.submit(kind, int(roots[0])).result(timeout=120)
    post_retraces = fr.retraces_since(marks)

    # -- gates: recovery bit-exact vs a SURVIVOR + zero acked loss -------
    survivor_spool = os.path.join(work, "survivor.npz")
    fr.replicas[fr.home].call(
        "spool_version", {"path": survivor_spool}, timeout_s=120
    )
    stats = fr.stats()
    fr.close(drain=True)
    survivor = checkpoint.load_version(survivor_spool, grid,
                                       writable=False)
    wal = open_wal(wal_dir)
    recovered = recover_version(wal_dir, wal, grid, kinds=kinds)
    wal.close()
    hr, hc, hv = survivor.E.to_host_coo()
    rr, rc_, rv = recovered.E.to_host_coo()
    bit_exact = (
        np.array_equal(np.asarray(hr), np.asarray(rr))
        and np.array_equal(np.asarray(hc), np.asarray(rc_))
        and np.array_equal(np.asarray(hv), np.asarray(rv))
    )
    have = set(zip(rr.tolist(), rc_.tolist()))
    lost = [
        p for p in acked
        if p not in have or (p[1], p[0]) not in have
    ]

    # latency decomposition from the STITCHED traces only (the
    # thread-fleet comparator's in-process traces would pollute the
    # router/ipc/child attribution)
    decomp = _trace_decomposition(obs_trace, [
        r for r in obs_trace.records()
        if r["labels"].get("fleet") == "process"
    ])

    out = {
        "metric": "serve_recovery_process_availability",
        "warning": "closed-loop (coordinated omission)",
        "unit": "fraction_ok",
        "value": round(availability, 4),
        "availability_pct": round(100 * availability, 2),
        "ok": bool(
            availability >= 0.95
            and not lost
            and bit_exact
            and post_retraces == 0
            and sigstop_detected
            and stats["promotions"] >= 1
            and stats["replacements"] >= 3  # 2 SIGKILLs + SIGSTOP
        ),
        "fleet": "process",
        "nqueries": nqueries,
        "reads_ok": ok,
        "reads_failed": failed,
        "read_retries": stats["read_retries"],
        "writes_acked": len(acked),
        "write_failures": write_failures,
        "acked_writes_lost": len(lost),
        "recovered_bit_exact": bit_exact,
        "post_recovery_retraces": post_retraces,
        "sigkills": stats["sigkills"],
        "sigstop_detected": sigstop_detected,
        "sigstop_detect_s": (
            round(detected_s, 3) if detected_s is not None else None
        ),
        "sigstop_reads_served": stop_ok,
        "promotions": stats["promotions"],
        "replacements": stats["replacements"],
        "respawn_failures": stats["respawn_failures"],
        "ipc_timeouts": sum(
            r["ipc_timeouts"] for r in stats["per_replica"].values()
        ),
        "final_home": stats["home"],
        "p50_ms": round(1e3 * _percentile(lat, 0.50), 2) if lat else None,
        "p99_ms": round(1e3 * _percentile(lat, 0.99), 2) if lat else None,
        "latency_decomposition_ms": decomp,
        "latency_split_ms": _stitched_split(decomp),
        "qps_under_kills": round(nqueries / wall_s, 2),
        # the replica-parallelism headline: N processes (own runtimes)
        # vs N threads behind one shared exec lock, same read burst.
        # READ WITH cpus: on a single-core image the processes cannot
        # physically parallelize, so the ratio measures the ISOLATION
        # TAX (IPC round trip + result copy); the parallel win needs
        # per-replica silicon (the multi-chip follow-up).
        "read_qps_process": round(proc_qps, 2),
        "read_qps_thread": round(thread_qps, 2),
        "parallel_speedup": round(proc_qps / thread_qps, 2),
        "cpus": os.cpu_count(),
        "recovered_nnz": int(len(rr)),
        "replicas": nreplicas,
        "scale": scale,
        "grid": [1, 1],
        "kinds": list(kinds),
        "load_s": round(load_s, 2),
        "wall_s": round(wall_s, 2),
        "wal_dir": wal_dir,
    }
    obs.gauge("serve.bench.recovery_availability", availability)
    if sidecar:
        try:
            out["obs_jsonl"] = obs.dump_jsonl()
        except Exception as e:  # telemetry must never fail the bench
            out["obs_error"] = str(e)
    return out


def run_shard(scale: int = SCALE, edgefactor: int = EDGEFACTOR) -> dict:
    """BENCH_SERVE_SHARD=1 — cross-host sharded serving (module
    docstring): partition scaling, bit-exactness, one-slice
    SIGKILL+respawn availability, zero post-warmup retraces, durable
    writes and whole-service recovery."""
    import tempfile
    import threading

    import numpy as np

    from combblas_tpu import obs
    from combblas_tpu.dynamic import DeltaBatch
    from combblas_tpu.parallel.grid import Grid
    from combblas_tpu.serve import (
        GraphEngine,
        ServeConfig,
        ShardedEngine,
    )

    sidecar = obs.enable_sidecar("serve-shard")
    nslices = int(os.environ.get("BENCH_SHARD_SLICES", "2"))
    nqueries = int(os.environ.get("BENCH_SERVE_QUERIES", "200"))
    nwrites = int(os.environ.get("BENCH_SHARD_WRITES", "8"))
    mode = os.environ.get("BENCH_SHARD_MODE", "process")
    home = tempfile.mkdtemp(prefix="combblas-shard-bench-")

    n = 1 << scale
    from combblas_tpu.utils.rmat import rmat_symmetric_coo_host

    rows, cols = rmat_symmetric_coo_host(42, scale, edgefactor)
    rng = np.random.default_rng(7)
    weights = (rng.random(len(rows)) + 0.1).astype(np.float32)
    kinds = ("bfs", "sssp")
    deg = np.bincount(rows, minlength=n)
    roots = rng.choice(np.flatnonzero(deg > 0), size=nqueries)
    stream = [
        (kinds[i % len(kinds)], int(r)) for i, r in enumerate(roots)
    ]
    probe = np.asarray(roots[:8], np.int32)

    # -- the unsharded comparator (also the bit-exactness oracle) --------
    grid = Grid.make(1, 1)
    eng = GraphEngine.from_coo(
        grid, rows, cols, n, weights=weights, kinds=kinds,
        keep_coo=True,
    )
    unsharded_bytes = int(eng.version.device_bytes())
    ref = {k: eng.execute(k, probe) for k in kinds}

    t0 = time.perf_counter()
    sh = ShardedEngine.build(
        rows, cols, nrows=n, nslices=nslices, weights=weights,
        kinds=kinds, home=home, mode=mode, warmup=True,
        hb_interval_s=0.1, hb_timeout_s=2.0,
    )
    boot_s = time.perf_counter() - t0
    per_slice = [int(b) for b in sh.version.device_bytes_per_slice]
    bytes_ratio = max(per_slice) / unsharded_bytes

    def _bit_exact() -> bool:
        for kind, key in (("bfs", "parents"), ("sssp", "dist")):
            got = sh.execute(kind, probe)
            if not np.array_equal(np.asarray(ref[kind][key]),
                                  np.asarray(got[key])):
                return False
            if kind == "bfs" and int(
                ref[kind]["batch_niter"]
            ) != int(got["batch_niter"]):
                return False
        return True

    exact_before = _bit_exact()

    # -- round-21 wire-protocol A/B: the same engine answers the probe
    #    batch under forced dense, forced sparse, then auto encoding.
    #    Hop payload (bytes_by_enc over the frontier fans only — the
    #    collect/final fetch is identical across modes) is the gated
    #    quantity: sparse must ship <= 0.20x the dense bytes without
    #    giving back more than 5% hop wall. Five INTERLEAVED rounds
    #    (each round runs all three modes back to back, so scheduler /
    #    allocator drift on a single-CPU runner lands on every mode
    #    equally); bytes are deterministic, wall takes the per-mode
    #    min to shrug off one-sided multi-second GC outliers.
    modes = ("dense", "sparse", "auto")
    walls: dict = {m: [] for m in modes}
    stats: dict = {}
    saved_mode = sh.frontier_mode
    for _ in range(5):
        for fmode in modes:
            sh.frontier_mode = fmode
            sh.execute("bfs", probe)
            walls[fmode].append(sh.last_exec_stats["hop_wall_s"])
            stats[fmode] = sh.last_exec_stats
    sh.frontier_mode = saved_mode
    enc_ab: dict = {}
    for fmode in modes:
        st = stats[fmode]
        hop_payload = sum(
            v for k, v in st["bytes_by_enc"].items()
            if k in ("sparse", "dense")
        )
        best = min(walls[fmode])
        enc_ab[fmode] = {
            "hops": st["hops"],
            "hop_payload_bytes": int(hop_payload),
            "bytes_out": int(st["bytes_out"]),
            "bytes_in": int(st["bytes_in"]),
            "enc_hops": dict(st["enc_hops"]),
            "frontier_nnz": [int(z) for z in st["frontier_nnz"]],
            "hop_wall_s": round(best, 5),
            "hop_ms_mean": round(1e3 * best / max(st["hops"], 1), 3),
        }
    wire_ratio = (
        enc_ab["sparse"]["hop_payload_bytes"]
        / max(enc_ab["dense"]["hop_payload_bytes"], 1)
    )
    hop_wall_ratio = (
        enc_ab["sparse"]["hop_wall_s"]
        / max(enc_ab["dense"]["hop_wall_s"], 1e-9)
    )

    # -- closed-loop stream through the batcher, one slice SIGKILLed
    #    mid-stream while the supervisor heals it ------------------------
    mark = sh.trace_mark()
    srv = sh.serve(ServeConfig(
        lane_widths=(1, 2, 4, 8, 16),
        max_queue=max(64, nqueries), max_wait_s=0.005,
        update_flush=1,
    ))
    srv.start()
    sh.start_supervisor(interval_s=0.05)
    kill_at = nqueries // 2
    victim = 0
    ok = failed = 0
    lat: list[float] = []
    t0 = time.perf_counter()
    for i, (kind, root) in enumerate(stream):
        if i == kill_at:
            sh.slices[victim].kill()  # SIGKILL under load
        ts = time.monotonic()
        try:
            srv.submit(kind, root).result(timeout=120)
            lat.append(time.monotonic() - ts)
            ok += 1
        except Exception:
            failed += 1
    wall_s = time.perf_counter() - t0
    deadline = time.monotonic() + 60
    while (
        sh._needs_rebuild
        or not all(sl.is_serving() for sl in sh.slices)
    ) and time.monotonic() < deadline:
        time.sleep(0.02)
    availability = ok / nqueries
    post_retraces = sh.retraces_since(mark)
    exact_after = _bit_exact()

    # -- two-phase writes through the server, then whole-service
    #    recovery reassembles the identical COO --------------------------
    present = set(zip(rows.tolist(), cols.tolist()))
    pool = rng.permutation(n).tolist()
    pairs = []
    for a, b in zip(pool[0::2], pool[1::2]):
        if a != b and (a, b) not in present and (b, a) not in present:
            pairs.append((int(a), int(b)))
        if len(pairs) >= nwrites:
            break
    acked = 0
    seq = 0
    for a, b in pairs:
        f = srv.submit_update([("insert", a, b), ("insert", b, a)])
        srv.pump_updates(force=True)
        f.result(timeout=120)
        acked += 1
        eng.swap(eng.apply_delta(DeltaBatch.from_ops(
            [("insert", a, b, 1.0), ("insert", b, a, 1.0)],
            start_seq=seq,
        )))
        seq += 2
    frontier = list(sh.version.frontier)
    coo_live = sh.to_host_coo()
    sh.stop_supervisor()
    srv.close()
    sh.close()
    t0 = time.perf_counter()
    sh2 = ShardedEngine.recover(home, mode=mode)
    recover_s = time.perf_counter() - t0
    coo_rec = sh2.to_host_coo()
    recovered_equal = all(
        (x is None and y is None)
        or np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(coo_live, coo_rec)
    )
    er, ec, _ev = eng.version.E.to_host_coo()
    order = np.argsort(
        np.asarray(er, np.int64) * n + np.asarray(ec, np.int64),
        kind="stable",
    )
    writes_match_unsharded = np.array_equal(
        np.asarray(er)[order], coo_rec[0]
    ) and np.array_equal(np.asarray(ec)[order], coo_rec[1])
    sh2.close()

    out = {
        "metric": "serve_shard_availability",
        "warning": "closed-loop (coordinated omission)",
        "unit": "fraction_ok",
        "value": round(availability, 4),
        "availability_pct": round(100 * availability, 2),
        "ok": bool(
            availability >= 0.99
            and bytes_ratio <= 0.60
            and exact_before
            and exact_after
            and post_retraces == 0
            and sh.replacements >= 1
            and acked == len(pairs)
            and recovered_equal
            and writes_match_unsharded
            and wire_ratio <= 0.20
            and hop_wall_ratio <= 1.05
        ),
        "wire": {
            "ratio": round(wire_ratio, 4),
            "hop_wall_ratio": round(hop_wall_ratio, 4),
            "frontier_mode": saved_mode,
            "per_mode": enc_ab,
        },
        "mode": mode,
        "slices": nslices,
        "nqueries": nqueries,
        "reads_ok": ok,
        "reads_failed": failed,
        "bit_exact_before_kill": exact_before,
        "bit_exact_after_respawn": exact_after,
        "post_warmup_retraces": post_retraces,
        "slice_deaths": sh.replacements,
        "replacements": sh.replacements,
        "device_bytes_unsharded": unsharded_bytes,
        "device_bytes_per_slice": per_slice,
        "per_slice_bytes_ratio": round(bytes_ratio, 4),
        "writes_acked": acked,
        "write_frontier": frontier,
        "recovered_coo_equal": recovered_equal,
        "writes_match_unsharded": writes_match_unsharded,
        "p50_ms": round(1e3 * _percentile(lat, 0.50), 2) if lat else None,
        "p99_ms": round(1e3 * _percentile(lat, 0.99), 2) if lat else None,
        "qps_under_kill": round(nqueries / wall_s, 2),
        "boot_s": round(boot_s, 2),
        "recover_s": round(recover_s, 2),
        "nnz": int(len(rows)),
        "scale": scale,
        "kinds": list(kinds),
        "cpus": os.cpu_count(),
        "home": home,
    }
    obs.gauge("serve.bench.shard_availability", availability)
    if sidecar:
        try:
            out["obs_jsonl"] = obs.dump_jsonl()
        except Exception as e:  # telemetry must never fail the bench
            out["obs_error"] = str(e)
    return out


def _emit_pool_summary(out: dict) -> int:
    """The bench headline contract (bench.py ``emit_summary``) for the
    standalone pool scenario: a compact truncation-proof final stdout
    line + BENCH_SUMMARY.json carrying the per-tenant breakdown."""
    rc = 0 if out.get("ok") else 1
    s = {
        "summary": 1,
        "metric": out.get("metric"),
        "value": out.get("value", 0.0),
        "median": out.get("p50_ms", out.get("value", 0.0)),
        "warning": out.get("warning"),
        "rc": rc,
        "per_tenant": out.get("per_tenant"),
        **device_fields(),
    }
    if out.get("wire") is not None:
        # shard scenario: per-hop wire-bytes + hop-latency breakdown
        # rides the summary line so truncated logs still carry it
        s["wire"] = out["wire"]
    path = os.environ.get("BENCH_SUMMARY_PATH", "BENCH_SUMMARY.json")
    try:
        with open(path, "w") as f:
            json.dump(s, f)
            f.write("\n")
    except OSError as e:
        s["summary_write_error"] = f"{path}: {e}"
    print(json.dumps(s), flush=True)
    return rc


def main():
    if os.environ.get("BENCH_SERVE_NET") == "1":
        # the open-loop net harness owns its own headline emission
        # (same contract, same BENCH_EMIT_SUMMARY=0 child-runner rule)
        from combblas_tpu.serve.net import loadgen

        sys.exit(loadgen.main())
    if os.environ.get("BENCH_SERVE_POOL") == "1":
        out = {**run_pool(), **device_fields()}
        print(json.dumps(out), flush=True)
        if os.environ.get("BENCH_EMIT_SUMMARY", "1") != "0":
            # STANDALONE contract: compact summary as the final line +
            # BENCH_SUMMARY.json, gate failures as the exit code.
            # Under bench.py's child runner (which sets
            # BENCH_EMIT_SUMMARY=0) the DETAIL line must stay last and
            # the exit code 0 — the parent parses the last line and
            # derives rc itself; a nonzero child exit would discard
            # the whole per-tenant payload as a "child crash".
            sys.exit(_emit_pool_summary(out))
        return
    if os.environ.get("BENCH_SERVE_SHARD") == "1":
        # device fields are read AFTER the run: the slices are gone, so
        # the router touching its own backend takes nothing from them
        out = {**run_shard(), **device_fields()}
        print(json.dumps(out), flush=True)
        if os.environ.get("BENCH_EMIT_SUMMARY", "1") != "0":
            # standalone contract (see the pool branch): summary line
            # + BENCH_SUMMARY.json, gate failures as the exit code
            sys.exit(_emit_pool_summary(out))
        return
    if os.environ.get("BENCH_SERVE_CHAOS") == "1":
        out = run_chaos()
    elif os.environ.get("BENCH_SERVE_MUTATE") == "1":
        out = run_mutate()
    elif os.environ.get("BENCH_SERVE_RECOVERY") == "1":
        if os.environ.get("BENCH_FLEET") == "process":
            out = run_recovery_process()
        else:
            out = run_recovery()
    else:
        out = run()
    print(json.dumps({**out, **device_fields()}), flush=True)


if __name__ == "__main__":
    main()
