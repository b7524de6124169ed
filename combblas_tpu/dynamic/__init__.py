"""``combblas_tpu.dynamic`` — the streaming graph-mutation lane.

PR 6 landed the READ half of dynamic serving: double-buffered
``GraphVersion`` hot-swap with surviving plan caches.  This package is
the WRITE half (the capability bar is the reference's in-place
``SpParMat::Prune`` / assign ops, PAPER.md §2), three layers:

1. **delta** (`delta.py`) — ``DeltaBuffer``: a bounded host-side COO
   delta log (insert / delete / upsert with a per-semiring combine on
   duplicate keys and a deterministic, vectorized fold), batched
   admission with reject-on-full backpressure, obs-visible depth/age.
2. **merge** (`merge.py`) — ``apply_delta(version, batch)``: fold a
   drained batch into the existing ``EllParMat`` tiles and their
   weighted / normalized / transpose twins PER TILE — rows whose
   degree-class slots still fit are patched in place, overflowing rows
   re-bucket into free padding slots, and a spill threshold falls back
   to a full rebuild — re-uploading only the touched bucket classes so
   same-shape swaps keep the zero-retrace guarantee, with counters
   making the incremental-vs-rebuild amortization measurable.
3. **refresh** (`refresh.py`) — warm-restart recompute:
   delta-frontier BFS/CC repair (re-expand only from the endpoints of
   changed edges; insert-only, by monotonicity) and PageRank restart
   from the previous vector, exposed as ``GraphEngine.refresh(kind)``.
4. **wal** (`wal.py`, round 16) — the durability layer: a
   schema-versioned append-only write-ahead log of acknowledged
   ``submit_update`` batches (torn-tail tolerant, fsync-policy knob)
   plus ``recover_version`` = latest valid ``utils.checkpoint``
   snapshot + WAL-suffix replay through ``apply_delta``, bit-exact
   with a never-crashed engine (docs/serving.md "Durability &
   self-healing").

``serve.api.Server`` wires it into traffic: ``submit_update()`` admits
mutations into the buffer, a dedicated mutation thread coalesces and
merges them OFF the execution lock, and ``swap_graph`` flips the
version atomically — reads stay hot while writes stream in
(``tests/test_serve_mutate.py`` holds the mix under load).  See
docs/dynamic.md.
"""

from .delta import (  # noqa: F401
    COMBINES,
    DeltaBatch,
    DeltaBuffer,
    DeltaOverflowError,
    OP_NAMES,
    fold_ops,
)
from .merge import (  # noqa: F401
    MergeState,
    MergeStats,
    apply_delta,
    bootstrap_state,
)
from .refresh import REFRESH_KINDS, refresh_analytic  # noqa: F401
from .wal import (  # noqa: F401
    RecoveryError,
    WriteAheadLog,
    open_wal,
    recover,
    recover_version,
)

__all__ = [
    "DeltaBuffer", "DeltaBatch", "DeltaOverflowError", "OP_NAMES",
    "COMBINES", "fold_ops",
    "apply_delta", "bootstrap_state", "MergeState", "MergeStats",
    "refresh_analytic", "REFRESH_KINDS",
    "WriteAheadLog", "open_wal", "recover", "recover_version",
    "RecoveryError",
]
