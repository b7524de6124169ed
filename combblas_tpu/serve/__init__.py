"""``combblas_tpu.serve`` — batched, backpressured graph-query serving
on a warm mesh.

The kernel library answers "how fast is one batch"; this subsystem
answers "how do a million independent single-root queries BECOME
batches". Four layers (docs/serving.md has the full architecture):

1. **engine** (`engine.py`) — ``GraphEngine``: one loaded graph
   (EllParMat + weighted/normalized/transposed twins, CSC companion,
   degree vectors) and a shape-bucketed plan cache, pre-warmed by
   ``warmup()`` so steady-state requests never trace or compile.
2. **batcher** (`batcher.py`) — lane-bucket assembly: coalesce
   single-root BFS/SSSP/PageRank/BC requests into the nearest
   power-of-two lane width, pad with ``models.PAD_ROOT``, scatter
   per-lane results back to request futures (pad lanes can never leak).
3. **scheduler** (`scheduler.py`) — bounded queue with
   reject-with-retry-after admission control, per-kind flush deadlines,
   per-request timeouts, and per-request error isolation.
4. **api** (`api.py`) — ``Server``: ``submit()/submit_many()/stats()``
   plus the single worker thread that owns the execution stream, the
   poisoned-batch bisection retrier, execution-time deadline
   enforcement, ``health()``, ``swap_graph()`` (atomic graph-version
   hot-swap, plan cache surviving), and the WRITE lane —
   ``submit_update()`` + a mutation thread coalescing edge deltas into
   incremental merges (``combblas_tpu.dynamic``, docs/dynamic.md)
   off the execution lock, reads staying hot throughout.
5. **faults** (`faults.py`) — deterministic fault injection: named
   failure points threaded through the worker path, armed with
   scripted/seeded/predicate rules so every recovery path (bisection,
   per-kind circuit breakers, worker backoff, swap rollback) is
   testable and chaos-benchable.
6. **pool** (`pool.py`, round 14) — ``EnginePool``/``PoolServer``:
   many resident tenant graphs behind one device — tenant → engine
   routing, byte-accounted LRU eviction (host COO retained, re-admit
   rebuilds bit-exact), per-tenant breakers/SLOs/fault injectors, and
   one worker thread arbitrated by weighted deficit-round-robin
   (reads AND write merges charge the tenant's share).
7. **fleet** (`fleet.py`, rounds 14/16) — ``FleetRouter``: N replica
   servers of one graph behind one front door —
   least-loaded routing with spillover (dead/closed/draining replicas
   attract no traffic), writes routed to a home replica and fanned
   out through the atomic swap, warm starts from
   ``utils.checkpoint.save_version`` GraphVersion snapshots; plus the
   round-16 self-healing layer: a supervisor thread detecting dead
   replica workers, quarantine (pending futures failed honestly),
   rebuild-from-checkpoint+WAL replacement, home PROMOTION at the
   write-ahead log's seqno frontier, ``drain``/``rolling_restart``,
   and bounded read retry on the next-best replica.  The durability
   substrate (``dynamic/wal.py`` WAL + ``Server``'s background
   checkpointer + ``from_recovery``) is docs/serving.md "Durability &
   self-healing".
8. **procfleet** (`procfleet.py` + `_procworker.py` + `frame.py` +
   `policy.py`, round 17) — ``ProcessFleet``: the same fleet with
   REAL crash domains — each replica is an OS subprocess hosting a
   ``Server`` on its own JAX runtime (no shared exec lock: honest
   replica parallelism) behind a length-prefixed JSON IPC channel
   with per-request deadlines.  Routing/supervision policy is shared
   with ``FleetRouter`` via ``policy.py``; liveness is process-level
   (``Popen.poll``, broken pipe, heartbeat timeout — a SIGSTOPped
   replica is detected as a HANG and routed around), replacements
   respawn warm from checkpoint+WAL, the dead-home promotion happens
   over IPC at the WAL frontier, versions fan out as checkpoint
   files (never pickled arrays), and ``ProcessFaultPlan`` scripts
   real SIGKILL/SIGSTOP chaos deterministically.
9. **net** (`net/`, round 19) — ``NetFrontend``/``NetClient``: the
   TCP front door — a versioned request/reply protocol over the
   shared frame codec (``frame.py``: procfleet and net speak ONE
   codec over two transports), fronting
   any backend above: tenant-header routing into the pool, wire
   deadlines propagating into the SLO budget, the whole error
   taxonomy mapped onto typed protocol status codes (a rejection is
   a wire reply, never a dropped connection), and the open-loop
   Poisson load harness (``net/loadgen.py``)
   whose latencies are measured from scheduled arrival time — no
   coordinated omission.
10. **shard** (`shard.py` + `_shardworker.py`, round 20) —
   ``ShardedEngine``: ONE huge graph partitioned over N slice
   processes (contiguous row slabs, each a rectangular EllParMat on
   its own JAX runtime — per-host resident bytes ~1/p), duck-typing
   ``GraphEngine`` so the batcher/scheduler/api/net stack above runs
   UNCHANGED on top.  Queries execute as router-driven
   bulk-synchronous hop loops (the same jitted step bodies as the
   unsharded while_loop — bfs/sssp answers bit-exact); writes run a
   two-phase per-slice WAL protocol under a VECTOR checkpoint
   frontier; a dead slice is quarantined, respawned from its slab
   snapshot + WAL suffix, and re-joined while the OTHER slices keep
   serving (docs/serving.md "Sharded serving").

Everything is wired into ``combblas_tpu.obs`` (queue-depth gauge,
occupancy/padding-waste/latency histograms, plan-cache and
``trace.serve`` counters); the served path is measured on the chip by
``python3 -m chipbench.run`` (``BENCHMARK.json``, ``PERF.md``).
"""

from .batcher import Request, assemble, bucket_width, scatter
from .engine import KINDS, GraphEngine, GraphVersion
from .faults import (
    FAULT_POINTS,
    FaultInjector,
    InjectedFault,
    ProcessFaultPlan,
)
from .scheduler import (
    BackpressureError,
    CircuitBreaker,
    CircuitBreakerOpen,
    DeficitRoundRobin,
    Scheduler,
    ServeConfig,
)
from .api import Server
from .pool import EnginePool, PoolServer
from .fleet import FleetRouter, ReplicaDeadError
from .procfleet import IpcTimeoutError, ProcessFleet, ReplicaProc
from .net import NetClient, NetFrontend
from .shard import ShardedEngine, ShardedGraphVersion, plan_partition
from .slo import ErrorBudget

__all__ = [
    "GraphEngine", "GraphVersion", "Server", "ServeConfig", "Scheduler",
    "BackpressureError", "CircuitBreaker", "CircuitBreakerOpen",
    "DeficitRoundRobin", "EnginePool", "PoolServer", "FleetRouter",
    "ReplicaDeadError",
    "ProcessFleet", "ReplicaProc", "IpcTimeoutError",
    "NetFrontend", "NetClient",
    "ShardedEngine", "ShardedGraphVersion", "plan_partition",
    "FaultInjector", "InjectedFault", "ProcessFaultPlan",
    "FAULT_POINTS", "ErrorBudget",
    "Request", "KINDS",
    "bucket_width", "assemble", "scatter",
]
