"""The ONE place the package's ``COMBBLAS_*`` knobs are parsed: the
serve, dynamic, obs and shard settings below.  Each accessor resolves
explicit argument > environment variable > default.  No knob chooses a
product's kernel (``parallel/spgemm.py:choose_spgemm_tier`` does, from
the operands' counts).

Env-var conventions shared by every knob: unset or empty means
"default"; for the integer knobs ``"0"`` also means default.
"""

from __future__ import annotations

import os

#: Dynamic-graph mutation knobs (round 11, docs/dynamic.md).
ENV_DYNAMIC_SPILL = "COMBBLAS_DYNAMIC_SPILL_FRAC"

#: Round-12 knob: headroom-aware bucket sizing — the slack fraction of
#: padding slots every ELL bucket class reserves at build so high-churn
#: dynamic graphs re-bucket instead of spilling (docs/dynamic.md;
#: counter ``dynamic.merge.headroom_used``).
ENV_DYNAMIC_HEADROOM = "COMBBLAS_DYNAMIC_HEADROOM"

#: Round-14 knobs: the multi-tenant engine pool and the replicated
#: serving fleet (docs/serving.md "Multi-tenant pool & fleet").
#: ``COMBBLAS_POOL_BYTE_BUDGET`` bounds the pool's resident DEVICE
#: bytes (LRU eviction past it; 0/unset = unbounded),
#: ``COMBBLAS_POOL_QUANTUM`` is the weighted-fair-queueing deficit
#: quantum (requests granted per round per unit weight), and
#: ``COMBBLAS_FLEET_REPLICAS`` the default ``FleetRouter.build``
#: replica count.
ENV_POOL_BYTE_BUDGET = "COMBBLAS_POOL_BYTE_BUDGET"
ENV_POOL_QUANTUM = "COMBBLAS_POOL_QUANTUM"
ENV_FLEET_REPLICAS = "COMBBLAS_FLEET_REPLICAS"

#: Round-15 knob: deterministic per-request trace sampling rate for the
#: serve path (``obs/trace.py``).  A request is traced iff obs is
#: enabled AND ``crc32(request id) mod 1e6 < rate * 1e6`` — same ids +
#: same rate = same sampled set on every replica.  Unset/empty/0 = no
#: tracing (the zero-cost default).
ENV_OBS_TRACE_SAMPLE = "COMBBLAS_OBS_TRACE_SAMPLE"

#: Round-16 knobs: the serve durability layer (docs/serving.md
#: "Durability & self-healing").  ``COMBBLAS_WAL`` names the directory
#: holding the write-ahead log and its checkpoints (unset/0/off = no
#: durability — the zero-cost default: one attribute read per write);
#: ``COMBBLAS_WAL_FSYNC`` the append fsync policy (``always`` — every
#: acknowledged write is on disk before its future exists — or ``off``,
#: the OS-buffered throughput mode); ``COMBBLAS_CHECKPOINT_EVERY`` the
#: merge count between automatic background snapshots;
#: ``COMBBLAS_CHECKPOINT_RETAIN`` how many snapshots are retained (the
#: corrupt-snapshot fallback depth).
ENV_WAL = "COMBBLAS_WAL"
ENV_WAL_FSYNC = "COMBBLAS_WAL_FSYNC"
ENV_CHECKPOINT_EVERY = "COMBBLAS_CHECKPOINT_EVERY"
ENV_CHECKPOINT_RETAIN = "COMBBLAS_CHECKPOINT_RETAIN"

#: Valid WAL fsync policies (vetted at the knob).
WAL_FSYNC_POLICIES = ("always", "off")

#: Round-18 knobs: the fleet observability plane (docs/observability.md
#: "Process-fleet observability").  ``COMBBLAS_FLEETLOG`` overrides the
#: supervision-timeline JSONL path the process fleet appends to
#: (default: ``fleetlog.jsonl`` under the fleet's workdir; unset/``0``/
#: ``off`` fall through to that default).  ``COMBBLAS_OBS_HB_METRICS_S``
#: is the minimum seconds between child registry snapshots piggybacked
#: on replica heartbeats (metrics federation — the fleet-scrape wire
#: cadence; unset/``0`` = default).
ENV_FLEETLOG = "COMBBLAS_FLEETLOG"
ENV_OBS_HB_METRICS_S = "COMBBLAS_OBS_HB_METRICS_S"

#: Round-19 knobs: the network front door (docs/serving.md "Network
#: front door").  ``COMBBLAS_NET_PORT`` is the TCP listen port
#: (unset/``0`` = OS-assigned ephemeral — read the bound port back
#: from ``NetFrontend.port``); ``COMBBLAS_NET_MAX_CONNS`` caps open
#: connections (past it a hello gets a typed ``backpressure`` wire
#: reply, never a silent close); ``COMBBLAS_NET_ACCEPT_BACKLOG`` is
#: the kernel ``listen()`` queue depth.
ENV_NET_PORT = "COMBBLAS_NET_PORT"
ENV_NET_MAX_CONNS = "COMBBLAS_NET_MAX_CONNS"
ENV_NET_ACCEPT_BACKLOG = "COMBBLAS_NET_ACCEPT_BACKLOG"

#: Round-21 knobs: the sharded hop wire protocol (docs/serving.md
#: "Sharded hop wire protocol").  ``COMBBLAS_SHARD_FRONTIER`` picks
#: the frontier encoding the router stamps on each bulk-synchronous
#: hop: ``sparse`` (COO triples of the live frontier), ``dense`` (the
#: r20 ``[n, W]`` operand), or ``auto`` (sparse until the frontier
#: crosses the density threshold, then dense per hop — the diropt
#: regime switch applied at the wire).  ``COMBBLAS_SHARD_DENSITY`` is
#: that threshold as a frontier-nnz fraction of ``n*W`` (auto mode
#: only).  ``COMBBLAS_SHARD_WIRE`` opts propagate's inherently-dense
#: ``q`` into bf16 wire encoding (``f32`` | ``bf16``; the router
#: obs-tracks the quantization error as
#: ``serve.shard.wire_quant_err``).
ENV_SHARD_FRONTIER = "COMBBLAS_SHARD_FRONTIER"
ENV_SHARD_DENSITY = "COMBBLAS_SHARD_DENSITY"
ENV_SHARD_WIRE = "COMBBLAS_SHARD_WIRE"

#: Valid sharded frontier encodings / wire dtypes (vetted at the knob,
#: the WAL_FSYNC precedent).
SHARD_FRONTIER_MODES = ("auto", "sparse", "dense")
SHARD_WIRE_MODES = ("f32", "bf16")

#: Structural-change fraction above which ``dynamic.apply_delta``
#: spills to a full rebuild (the incremental path's amortization bound).
DEFAULT_DYNAMIC_SPILL_FRAC = 0.10
#: Default bucket-slot headroom: none (static graphs pay no padding
#: tax; dynamic engines opt in via from_coo(headroom=) or the env).
DEFAULT_DYNAMIC_HEADROOM = 0.0
#: Pool defaults (round 14): unbounded resident bytes (an operator
#: opts into eviction by setting a budget) and a 16-request WFQ
#: quantum per unit weight per round.
DEFAULT_POOL_BYTE_BUDGET = 0
DEFAULT_POOL_QUANTUM = 16
DEFAULT_FLEET_REPLICAS = 2
#: Durability defaults (round 16): fsync every acknowledged write
#: (durability-first; ``off`` is the opt-out), snapshot every 8 merges,
#: retain 2 snapshots (current + the corrupt-fallback predecessor).
DEFAULT_WAL_FSYNC = "always"
DEFAULT_CHECKPOINT_EVERY = 8
DEFAULT_CHECKPOINT_RETAIN = 2
#: Federation default (round 18): snapshot the child registry onto the
#: heartbeat at most once a second — fresh enough for scrape cadences,
#: cheap enough to vanish in the heartbeat noise.
DEFAULT_OBS_HB_METRICS_S = 1.0
#: Net front-door defaults (round 19): ephemeral port, 512 connection
#: slots (a thread apiece — thread-per-connection's practical ceiling,
#: not a protocol limit), a 128-deep kernel accept queue.
DEFAULT_NET_PORT = 0
DEFAULT_NET_MAX_CONNS = 512
DEFAULT_NET_ACCEPT_BACKLOG = 128
#: Sharded-wire defaults (round 21): adaptive frontier encoding with
#: dense fallback once the live frontier fills a quarter of the
#: ``[n, W]`` operand (past ~0.25 the per-entry triple overhead —
#: 5-9 B vs 4 B — plus scatter work loses to the dense memcpy), and
#: f32 on the wire (bf16 is the explicit opt-in: it halves propagate's
#: hop bytes but trades bit-exactness for allclose).
DEFAULT_SHARD_FRONTIER = "auto"
DEFAULT_SHARD_DENSITY = 0.25
DEFAULT_SHARD_WIRE = "f32"


def _str_env(name: str) -> str | None:
    v = os.environ.get(name)
    return v if v else None


def _int_env(name: str) -> int | None:
    """Unset, empty, and "0" all mean "use the default"."""
    v = os.environ.get(name)
    if not v:
        return None
    return int(v) or None


def dynamic_headroom(given: float | None = None) -> float:
    """Bucket-slot headroom fraction: explicit argument >
    ``COMBBLAS_DYNAMIC_HEADROOM`` > 0.  Clamped to >= 0 (a negative
    headroom would under-allocate the real rows)."""
    if given is not None:
        return max(float(given), 0.0)
    v = os.environ.get(ENV_DYNAMIC_HEADROOM)
    return max(float(v), 0.0) if v else DEFAULT_DYNAMIC_HEADROOM


def pool_byte_budget(given: int | None = None) -> int:
    """Resident-device-byte budget of a serve ``EnginePool``: explicit
    argument > ``COMBBLAS_POOL_BYTE_BUDGET`` > unbounded.  0 (and the
    usual unset/empty) means UNBOUNDED — eviction is opt-in."""
    if given is not None:
        return max(int(given), 0)
    v = _int_env(ENV_POOL_BYTE_BUDGET)
    return DEFAULT_POOL_BYTE_BUDGET if v is None else max(v, 0)


def pool_quantum(given: int | None = None) -> int:
    """Weighted-fair-queueing deficit quantum (requests per round per
    unit weight): explicit argument > ``COMBBLAS_POOL_QUANTUM`` > 16."""
    if given is not None:
        return max(int(given), 1)
    v = _int_env(ENV_POOL_QUANTUM)
    return DEFAULT_POOL_QUANTUM if v is None else max(v, 1)


def fleet_replicas(given: int | None = None) -> int:
    """Default ``FleetRouter.build`` replica count: explicit argument >
    ``COMBBLAS_FLEET_REPLICAS`` > 2."""
    if given is not None:
        return max(int(given), 1)
    v = _int_env(ENV_FLEET_REPLICAS)
    return DEFAULT_FLEET_REPLICAS if v is None else max(v, 1)


def obs_trace_sample(given: float | None = None) -> float:
    """Per-request trace sampling rate in [0, 1]: explicit argument >
    ``COMBBLAS_OBS_TRACE_SAMPLE`` > 0 (off).  Clamped to [0, 1]."""
    if given is None:
        v = os.environ.get(ENV_OBS_TRACE_SAMPLE)
        given = float(v) if v else 0.0
    return min(max(float(given), 0.0), 1.0)


def wal_dir(given: str | None = None) -> str | None:
    """The serve durability directory (WAL + checkpoints), or ``None``
    when durability is disabled: explicit argument >
    ``COMBBLAS_WAL`` > off.  ``0``/``off``/``none`` (argument or env)
    disable explicitly."""
    v = os.environ.get(ENV_WAL) if given is None else given
    if v is None or v.strip().lower() in ("", "0", "off", "none"):
        return None
    return os.path.abspath(v)


def wal_fsync(given: str | None = None) -> str:
    """WAL append fsync policy: explicit argument >
    ``COMBBLAS_WAL_FSYNC`` > ``always``.  A bogus value raises naming
    the knob instead of
    surfacing as a silent durability downgrade."""
    v = _str_env(ENV_WAL_FSYNC) if given is None else given
    if v is None:
        return DEFAULT_WAL_FSYNC
    if v not in WAL_FSYNC_POLICIES:
        raise ValueError(
            f"{ENV_WAL_FSYNC} must be one of "
            f"{'|'.join(WAL_FSYNC_POLICIES)}; got {v!r}"
        )
    return v


def fleetlog_path(given: str | None = None) -> str | None:
    """Supervision-timeline JSONL path override, or ``None`` to use the
    fleet's own default (``fleetlog.jsonl`` under its workdir):
    explicit argument > ``COMBBLAS_FLEETLOG`` > fleet default.
    ``0``/``off``/``none``/empty fall through to the default — the
    wal_dir convention."""
    v = os.environ.get(ENV_FLEETLOG) if given is None else given
    if v is None or v.strip().lower() in ("", "0", "off", "none"):
        return None
    return os.path.abspath(v)


def obs_hb_metrics_interval(given: float | None = None) -> float:
    """Minimum seconds between child registry snapshots piggybacked on
    replica heartbeats (metrics federation): explicit argument >
    ``COMBBLAS_OBS_HB_METRICS_S`` > 1.0.  Clamped >= 0.05 so a typo
    cannot turn every heartbeat into a full registry serialization."""
    if given is None:
        v = os.environ.get(ENV_OBS_HB_METRICS_S)
        given = float(v) if v else 0.0
    given = float(given)
    if given <= 0.0:
        return DEFAULT_OBS_HB_METRICS_S
    return max(given, 0.05)


def _vet_int(name: str, v, what: str) -> int:
    """Integer-knob vetting shared by the round-19 net knobs: a bogus
    value raises NAMING the knob (the WAL_FSYNC precedent)
    instead of surfacing as a bare ``int()`` traceback from deep
    inside socket setup."""
    try:
        return int(v)
    except (TypeError, ValueError):
        raise ValueError(
            f"{name} must be {what}; got {v!r}"
        ) from None


def net_port(given: int | str | None = None) -> int:
    """The front door's TCP listen port: explicit argument >
    ``COMBBLAS_NET_PORT`` > 0 (OS-assigned ephemeral).  Vetted to
    [0, 65535], raising naming the knob."""
    v = os.environ.get(ENV_NET_PORT) if given is None else given
    if v is None or v == "":
        return DEFAULT_NET_PORT
    p = _vet_int(ENV_NET_PORT, v, "an integer port (0 = ephemeral)")
    if not (0 <= p <= 65535):
        raise ValueError(
            f"{ENV_NET_PORT} must be in [0, 65535]; got {v!r}"
        )
    return p


def net_max_conns(given: int | str | None = None) -> int:
    """Open-connection cap of the net frontend: explicit argument >
    ``COMBBLAS_NET_MAX_CONNS`` > 512.  ``0``/unset = default; clamped
    >= 1 (a zero-slot front door would reject its own hello)."""
    v = os.environ.get(ENV_NET_MAX_CONNS) if given is None else given
    if v is None or v == "":
        return DEFAULT_NET_MAX_CONNS
    n = _vet_int(ENV_NET_MAX_CONNS, v, "an integer connection cap")
    return DEFAULT_NET_MAX_CONNS if n == 0 else max(n, 1)


def net_accept_backlog(given: int | str | None = None) -> int:
    """Kernel ``listen()`` backlog: explicit argument >
    ``COMBBLAS_NET_ACCEPT_BACKLOG`` > 128.  ``0``/unset = default;
    clamped >= 1."""
    v = (
        os.environ.get(ENV_NET_ACCEPT_BACKLOG)
        if given is None else given
    )
    if v is None or v == "":
        return DEFAULT_NET_ACCEPT_BACKLOG
    n = _vet_int(ENV_NET_ACCEPT_BACKLOG, v, "an integer backlog")
    return DEFAULT_NET_ACCEPT_BACKLOG if n == 0 else max(n, 1)


def shard_frontier(given: str | None = None) -> str:
    """Sharded hop frontier encoding: explicit argument >
    ``COMBBLAS_SHARD_FRONTIER`` > ``auto``.  A bogus value raises
    naming the knob (the WAL_FSYNC vetting precedent) instead of
    surfacing as a silently-dense wire."""
    v = _str_env(ENV_SHARD_FRONTIER) if given is None else given
    if v is None:
        return DEFAULT_SHARD_FRONTIER
    if v not in SHARD_FRONTIER_MODES:
        raise ValueError(
            f"{ENV_SHARD_FRONTIER} must be one of "
            f"{'|'.join(SHARD_FRONTIER_MODES)}; got {v!r}"
        )
    return v


def shard_density(given: float | str | None = None) -> float:
    """Auto-mode dense-fallback threshold as a frontier-nnz fraction
    of ``n*W``: explicit argument > ``COMBBLAS_SHARD_DENSITY`` > 0.25.
    ``0``/unset = default; vetted to (0, 1] — a fraction above 1 can
    never trigger and reads as a typo'd percentage."""
    v = os.environ.get(ENV_SHARD_DENSITY) if given is None else given
    if v is None or v == "":
        return DEFAULT_SHARD_DENSITY
    try:
        f = float(v)
    except (TypeError, ValueError):
        raise ValueError(
            f"{ENV_SHARD_DENSITY} must be a fraction in (0, 1]; "
            f"got {v!r}"
        ) from None
    if f == 0:
        return DEFAULT_SHARD_DENSITY
    if not (0.0 < f <= 1.0):
        raise ValueError(
            f"{ENV_SHARD_DENSITY} must be a fraction in (0, 1]; "
            f"got {v!r}"
        )
    return f


def shard_wire(given: str | None = None) -> str:
    """Sharded dense-payload wire dtype (propagate's ``q``): explicit
    argument > ``COMBBLAS_SHARD_WIRE`` > ``f32``.  A bogus value
    raises naming the knob instead of surfacing as a silent precision
    downgrade."""
    v = _str_env(ENV_SHARD_WIRE) if given is None else given
    if v is None:
        return DEFAULT_SHARD_WIRE
    if v not in SHARD_WIRE_MODES:
        raise ValueError(
            f"{ENV_SHARD_WIRE} must be one of "
            f"{'|'.join(SHARD_WIRE_MODES)}; got {v!r}"
        )
    return v


def checkpoint_every(given: int | None = None) -> int:
    """Merges between automatic background snapshots: explicit
    argument > ``COMBBLAS_CHECKPOINT_EVERY`` > 8."""
    if given is not None:
        return max(int(given), 1)
    v = _int_env(ENV_CHECKPOINT_EVERY)
    return DEFAULT_CHECKPOINT_EVERY if v is None else max(v, 1)


def checkpoint_retain(given: int | None = None) -> int:
    """Snapshots retained after an automatic checkpoint: explicit
    argument > ``COMBBLAS_CHECKPOINT_RETAIN`` > 2.  Clamped >= 1 —
    retaining zero snapshots would delete the one recovery just
    needs."""
    if given is not None:
        return max(int(given), 1)
    v = _int_env(ENV_CHECKPOINT_RETAIN)
    return DEFAULT_CHECKPOINT_RETAIN if v is None else max(v, 1)


def dynamic_spill_frac() -> float:
    """Structural-change fraction above which the incremental merge
    spills to a full rebuild (``dynamic.merge.spill{reason=threshold}``).
    """
    v = os.environ.get(ENV_DYNAMIC_SPILL)
    return float(v) if v else DEFAULT_DYNAMIC_SPILL_FRAC
