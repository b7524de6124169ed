"""``combblas_tpu.obs`` — structured telemetry for the hot paths.

The reference ships a whole TIMING subsystem — global ``cblas_*`` phase
counters compiled in under ``#ifdef TIMING`` (``CombBLAS.h:77-102``) and
per-app tables printed after each run (``TopDownBFS.cpp:472-479``). This
package is its structured, machine-readable replacement, three layers:

1. **metrics registry** (``metrics.py``) — counters/gauges/histograms
   with labels for scalar facts: SpGEMM symbolic vs realized fill-in,
   redistribute/bucket drop counts, compile-cache hit/miss, per-op
   load imbalance, jit trace counts, BFS lru-cache growth.
2. **span/trace layer** (``spans.py``) — nested named wall-time spans
   wrapping ``jax.profiler.TraceAnnotation`` (host spans line up with
   the device profiler timeline), with attached per-iteration events
   (BFS hop + frontier nnz, MCL round + chaos, SUMMA stage).
3. **sinks** (``sinks.py``) — the in-memory per-app table, a
   schema-versioned JSONL exporter, host-side multi-process merge, and
   a device psum path for add-monoid counters.

Round 15 adds the production serving surfaces (docs/observability.md
"Serving observability"): **per-request tracing** (``trace.py`` —
deterministic-sampled stage decompositions that sum to the e2e
latency), the **flight recorder** (``recorder.py`` — always-on
bounded ring dumped on failure as ``combblas_tpu.flightrec/v1``), and
the **live export surface** (``export.py`` — Prometheus text
exposition with reservoir quantiles + the stdlib-HTTP scrape thread
``Server.serve_metrics`` attaches).

COST CONTRACT: everything is guarded by the module-level ``ENABLED``
flag, checked before any dict work — with telemetry off, an
instrumented call site costs one attribute read (and ``span`` returns a
shared null context manager). Instrumentation lives HOST-SIDE only: no
host callbacks or extra syncs are ever inserted into jitted code;
counters recorded inside jit-traced Python count traces (retraces), not
executions, and device facts are only read back where a host sync
already exists — or when ``DEVICE_SYNC`` is explicitly opted into (CPU
debugging; a readback is a host sync the timed path does not have).

Usage::

    from combblas_tpu import obs
    obs.enable(jsonl_path="trace.jsonl")
    with obs.span("bfs", scale=20):
        ...
        obs.span_event("frontier", hop=3, nnz=1234)
    obs.count("redistribute.dropped", 0)
    obs.dump_jsonl()

See docs/observability.md for the event schema and worked examples.
"""

from __future__ import annotations

import functools
import os

from .metrics import MetricsRegistry
from .sinks import (
    FLEETLOG_SCHEMA,
    FLIGHTREC_SCHEMA,
    SCHEMA,
    SCHEMA_VERSION,
    aggregate,
    encode_records,
    merge_jsonl_files,
    parse_jsonl,
    psum_counters,
    quantile_summary,
    quantiles,
    validate_record,
    write_jsonl,
)
from .spans import NULL_SPAN, SpanTracker
from . import opnames
from . import trace as _trace

#: Master switch, checked at every instrumentation site BEFORE any work.
#: Off by default: the hot paths must cost nothing unless telemetry is
#: asked for (env COMBBLAS_OBS=1 or obs.enable()).
ENABLED: bool = os.environ.get("COMBBLAS_OBS", "0") not in ("", "0")

#: Opt-in for instrumentation that READS DEVICE SCALARS (e.g. realized
#: SpGEMM output nnz): each is a host sync. Never enable in timed
#: sections.
DEVICE_SYNC: bool = os.environ.get("COMBBLAS_OBS_SYNC", "0") not in ("", "0")

registry = MetricsRegistry()
_spans = SpanTracker()
_providers: list = []
_jsonl_path: str | None = None
_hooks_installed = False


# --- lifecycle --------------------------------------------------------------


def enable(jsonl_path: str | None = None, *, device_sync: bool | None = None,
           install_hooks: bool = True) -> None:
    """Turn telemetry on (idempotent). ``jsonl_path`` configures the
    default ``dump_jsonl`` target; ``device_sync`` opts into
    readback-requiring metrics (CPU debugging only)."""
    global ENABLED, DEVICE_SYNC, _jsonl_path
    ENABLED = True
    if device_sync is not None:
        DEVICE_SYNC = bool(device_sync)
    if jsonl_path is not None:
        _jsonl_path = jsonl_path
    if install_hooks:
        install_jax_hooks()


def disable() -> None:
    global ENABLED
    ENABLED = False


def enabled() -> bool:
    return ENABLED


def reset() -> None:
    """Clear every metric, span, event, per-request trace and published
    instruction table (the flag is untouched)."""
    registry.clear()
    _spans.clear()
    _trace.clear()
    opnames.clear()


def reset_spans() -> None:
    """Clear only the (seconds, calls) span table (the timers-shim
    reset) — the structured span log and events belong to the obs
    subsystem and survive; use ``reset()`` for a full wipe."""
    _spans.clear_table()


# --- writers ----------------------------------------------------------------


def count(name: str, value=1, **labels) -> None:
    if not ENABLED:
        return
    registry.count(name, value, **labels)


def gauge(name: str, value, **labels) -> None:
    if not ENABLED:
        return
    registry.gauge(name, value, **labels)


def observe(name: str, value, **labels) -> None:
    if not ENABLED:
        return
    registry.observe(name, value, **labels)


def span(name: str, *, sync=None, force: bool = False, **attrs):
    """Context manager timing the enclosed block under ``name``.

    ``sync``: optional array/pytree to ``block_until_ready`` before the
    timer closes (async dispatch must not hide device time). ``force``
    records even when telemetry is globally off (the ``utils/timers``
    compatibility path) — but then only into the (seconds, calls) table,
    like the old timers, never the per-call structured log. ``attrs``
    become span attributes in the export.
    """
    if not (ENABLED or force):
        return NULL_SPAN
    return _spans.open(name, True, sync=sync, log=ENABLED, **attrs)


def spanned(name: str):
    """Decorator: the whole call under ``span(name)`` (a constructor, a
    boot step).  One flag read a call with telemetry off."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kw):
            with span(name):
                return fn(*args, **kw)
        return wrapper
    return deco


def span_event(name: str, **fields) -> None:
    """Attach a per-iteration record (hop/round/stage) to the innermost
    open span — or log it top-level if no span is open."""
    if not ENABLED:
        return
    _spans.event(name, **fields)


# --- per-request tracing (round 15, obs/trace.py) ---------------------------


def request_trace(rid, kind: str | None = None,
                  tenant: str | None = None):
    """Open a deterministic-sampled per-request trace (None when obs
    is off or the sampler declines ``rid``) — the serve read lane's
    entry.  One function call + flag check when disabled."""
    if not ENABLED:
        return None
    return _trace.begin(rid, "serve.request", kind=kind, tenant=tenant)


def update_trace(rid, tenant: str | None = None):
    """The write lane's trace entry (``name="serve.update"``)."""
    if not ENABLED:
        return None
    return _trace.begin(rid, "serve.update", tenant=tenant)


def trace_records() -> list[dict]:
    """Completed per-request trace records (schema kind ``trace``)."""
    return _trace.records()


def spans() -> list[dict]:
    """Closed spans in closing order (a copy of the structured log):
    ``name``, ``path`` (parents joined by ``/``), ``ts`` (``time.time``)
    and ``t0`` (``perf_counter``) at the start, ``wall_s``, and where
    present ``attrs``, ``parts``, ``events``, ``failed``.  (The name is
    also the submodule's: ``from combblas_tpu.obs.spans import ...``
    reaches that, the attribute ``obs.spans`` is this reader.)"""
    return _spans.records()


def events() -> list[dict]:
    """Events recorded with no span open (a copy): ``name``, ``ts``,
    ``t`` (``perf_counter``) and the event's own fields."""
    return _spans.top_events()


def prune_labels(**labels) -> int:
    """Drop every registry series labeled with ALL the given pairs
    (tenant-churn label-space hygiene; works whether or not telemetry
    is currently enabled — stale series from an earlier enabled phase
    must still be removable)."""
    return registry.prune_labels(**labels)


# --- providers (pull-style gauges, polled at export time) -------------------


def register_provider(fn) -> None:
    """Register a zero-arg callable that refreshes gauges (via
    ``obs.gauge``) when a report/dump is produced — e.g. lru_cache
    hit/miss/size exporters that would be wasteful to push on every
    cache access."""
    if fn not in _providers:
        _providers.append(fn)


def _run_providers() -> None:
    if not ENABLED:
        return
    for fn in list(_providers):
        try:
            fn()
        except Exception:  # a broken provider must not kill the export
            registry.count("obs.provider_errors")


# --- readers / sinks --------------------------------------------------------


def report(reset: bool = False) -> dict[str, tuple[float, int]]:
    """The per-app timing table: {span name: (seconds, calls)} — what the
    reference prints after each run (TopDownBFS.cpp:472-479).
    ``reset=True`` clears only this table, not the structured span
    log/events (``reset()`` is the full wipe)."""
    out = _spans.table()
    if reset:
        _spans.clear_table()
    return out


def span_seconds(name: str) -> float:
    return _spans.seconds(name)


def print_report(reset: bool = False) -> None:
    for k, (sec, n) in report(reset=reset).items():
        print(f"{k:32s} {sec:10.4f}s  x{n}")


def metrics_snapshot() -> list[dict]:
    _run_providers()
    return registry.snapshot()


def dump_jsonl(path: str | None = None, *, process: int | None = None,
               nprocs: int | None = None) -> str:
    """Write the full telemetry state as one schema-versioned JSONL file
    (meta line, spans, events, metrics). Default path is the one given
    to ``enable``; the file is rewritten whole on each call."""
    path = path or _jsonl_path
    if path is None:
        raise ValueError("no JSONL path: pass one or enable(jsonl_path=...)")
    if process is None or nprocs is None:
        try:
            import jax

            process = jax.process_index() if process is None else process
            nprocs = jax.process_count() if nprocs is None else nprocs
        except Exception:
            process, nprocs = process or 0, nprocs or 1
    _run_providers()
    records = encode_records(
        registry.snapshot(), _spans, process=process, nprocs=nprocs,
        traces=_trace.records(),
    )
    return write_jsonl(path, records)


# --- jax.monitoring bridge --------------------------------------------------

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: JAX's own seconds for what a first call of a program costs, by the
#: name of the span event each becomes.  ``compile`` is JAX's whole
#: backend-compile interval, so on a persistent-cache hit it CONTAINS
#: the ``fetch`` fired inside it, as an outer function's ``trace`` does
#: its inner jits': a reader sums them as intervals ``[t - s, t]``.
JAX_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "fetch",
}


def install_jax_hooks() -> bool:
    """Bridge ``jax.monitoring`` into telemetry (idempotent):
    persistent-compile-cache hits/misses become the ``compile_cache.*``
    counters, and JAX's trace / lower / fetch / compile durations
    (``JAX_DURATION_EVENTS``) become span events ``{name, s, t}`` on the
    innermost open span of the thread they ran on, top-level where none
    is open: what a boot paid for its programs, where it paid it."""
    global _hooks_installed
    if _hooks_installed:
        return True
    try:
        from jax import monitoring
    except Exception:
        return False

    def _on_event(event: str, **kw):
        if not ENABLED:
            return
        if event == _CACHE_HIT_EVENT:
            registry.count("compile_cache.hits")
        elif event == _CACHE_MISS_EVENT:
            registry.count("compile_cache.misses")

    def _on_duration(event: str, duration_secs: float, **kw):
        if not ENABLED:
            return
        name = JAX_DURATION_EVENTS.get(event)
        if name is not None:
            _spans.event(name, s=float(duration_secs))

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    # seed the cache counters so every dump carries them, hit or not
    registry.count("compile_cache.hits", 0)
    registry.count("compile_cache.misses", 0)
    _hooks_installed = True
    return True


#: The per-request tracing module (``obs.trace`` — sampling knobs,
#: ``stage_summary`` for latency decompositions).
trace = _trace

__all__ = [
    "ENABLED", "DEVICE_SYNC", "SCHEMA", "SCHEMA_VERSION",
    "FLIGHTREC_SCHEMA", "FLEETLOG_SCHEMA",
    "enable", "disable", "enabled", "reset",
    "reset_spans",
    "count", "gauge", "observe", "span", "spanned", "span_event",
    "request_trace", "update_trace", "trace_records", "spans", "events",
    "prune_labels",
    "register_provider", "report", "print_report", "span_seconds",
    "metrics_snapshot", "dump_jsonl", "install_jax_hooks",
    "parse_jsonl", "merge_jsonl_files", "aggregate", "validate_record",
    "encode_records", "write_jsonl", "psum_counters", "registry",
    "quantiles", "quantile_summary", "trace",
    "MetricsRegistry", "SpanTracker", "NULL_SPAN",
]
