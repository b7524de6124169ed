"""GraphEngine — a loaded graph plus a shape-bucketed warm plan cache.

The batch kernels make TPUs pay off only when (a) requests share one
launch and (b) the launched executable already exists. The engine owns
both halves for one graph:

* the loaded matrices and derived artifacts: the structural
  ``EllParMat`` (BFS/BC/PageRank-structure), its weighted twin (SSSP),
  the column-normalized PageRank transition matrix + dangling vector,
  the transpose (BC on directed graphs) and the row/column degree
  vectors (``coldeg``) — built host-side once at load, uploaded once;
  where BFS is served, the CSC companion (``csc_companion()``): the
  column structure a thin level of the BFS plan walks its frontier in;
* a **plan cache** keyed by (query kind, lane width): each plan is one
  jitted program whose trace increments both a host-side counter and
  the ``trace.serve`` obs counter (trace-time side effects count
  RETRACES, not executions — the zero-retrace acceptance gate), so
  ``warmup()`` over the configured lane buckets guarantees steady-state
  requests never trace or compile.

The loaded state lives on a ``GraphVersion`` and plans take their
matrices as CALL-TIME jit arguments, so ``swap()`` can atomically
replace the whole graph under the execution lock while the plan cache
survives (zero retraces for same-shape versions) — the hot-swap half
of dynamic-graph serving; ``build_version()`` constructs the next
generation off-lock (double-buffered).

The engine is synchronous and thread-safe: plan building, ``warmup``
and ``execute`` serialize on one internal lock (one execution stream —
a caller-thread ``warmup()`` cannot race the api worker's batches);
results come back as HOST numpy arrays, so ``execute`` is the
device→host sync point.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from .. import obs
from ..obs.spans import NULL_SPAN
from ..models import PAD_ROOT

#: Query kinds the engine can build plans for.  ``"propagate"`` (round
#: 12) is the graph-ML lane: lane w of a batch answers "the k-hop
#: propagated feature row of vertex w" via the batched SpMM kernels
#: (models/propagate.py) — it needs a feature table
#: (``from_coo(features=...)``).
KINDS = ("bfs", "sssp", "pagerank", "bc", "propagate")



class _PartClock:
    """``with clock(part):`` around one part of ``execute``: appends
    ``(part, perf_counter at its end)`` to the caller's list and writes
    a ``serve.execute.<part>`` annotation on the profiler's clock."""

    __slots__ = ("parts", "_part", "_ann")

    def __init__(self, parts: list):
        self.parts = parts

    def __call__(self, part: str):
        self._part = part
        return self

    def __enter__(self):
        import jax

        self._ann = jax.profiler.TraceAnnotation(
            "serve.execute." + self._part
        )
        self._ann.__enter__()

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self.parts.append((self._part, time.perf_counter()))
        return False


def _no_clock(part: str):
    """What ``execute`` enters when nobody asked for its parts."""
    return NULL_SPAN


#: The kinds whose program tallies its class sweeps
#: (``ellmat._ell_class_sweeps``), and the output it returns the tally
#: in: after the two result blocks and the iteration count of "bfs" and
#: "sssp"; after "bc"'s one block, its depth and its sweeps by phase.
_TALLIED = frozenset({"bfs", "sssp", "bc"})
_TALLY_AT = 3


@dataclasses.dataclass(slots=True)
class _Launched:
    """A dispatched batch between ``launch`` and ``collect``: the
    plan's un-read device results and what reading them back needs."""

    kind: str
    width: int
    res: object  # the plan's outputs, still on the device
    mark: object  # the part clock of a traced batch, else ``_no_clock``
    feat_dim: int  # of the version it runs on: a swap may land first
    #: telemetry on, a kind in ``_TALLIED``: what weighs its tally, of
    #: the version it runs on too (``GraphEngine._swept``)
    swept: tuple = ()
    waited: bool = False
    #: what ``collect`` made of the tally (``_count_sweeps``), for the
    #: batch's ``execute`` stage record
    work: dict | None = None


@dataclasses.dataclass
class _Plan:
    """One warm executable: (kind, width) -> jitted program + metadata."""

    kind: str
    width: int
    fn: object  # jitted callable
    traces: int = 0  # incremented at TRACE time (retrace counter)
    executions: int = 0
    lower: object = None  # sources -> the same program, lowered


@dataclasses.dataclass
class GraphVersion:
    """One immutable generation of loaded graph state — everything a
    plan's operands come from, bundled so the engine can swap it
    ATOMICALLY (one reference flip under the execution lock) while the
    plan cache survives.

    Plans are jitted over these matrices as ARGUMENTS (not closed-over
    constants), so a swap to a version with identical operand shapes
    (same nrows/ncols and ELL tile widths) re-uses every compiled
    executable: zero retraces. A version with different shapes serves
    correctly but pays one retrace per (kind, width) on first use —
    visible in ``trace.serve`` / ``retraces_since``.
    """

    nrows: int
    ncols: int
    nnz: int
    E: object                      # structural EllParMat
    deg: object                    # host [nrows] in-degree
    outdeg: object                 # host [ncols] out-degree
    E_weighted: object = None      # None => unit weights (falls back to E)
    P_ell: object = None           # pagerank transition matrix
    dangling: object = None        # pagerank dangling DistVec
    ET: object = None              # None => symmetric (E is its own T)
    csc: object = None             # CSC companion (indptr, rowidx),
    #                                an operand of the BFS plan
    csc_current: bool = True       # False: ``csc`` has the shapes the
    #                                plans were traced with but not this
    #                                version's edges (a structural merge,
    #                                a snapshot without one): every
    #                                level is swept until
    #                                ``csc_companion()`` rebuilds it
    coldeg: object = None          # lazy col-degree DistVec cache
    host_coo: tuple | None = None  # retained iff keep_coo=True
    host_weights: object = None    # deduped weights (the mutation lane)
    X: object = None               # propagate feature table (row-aligned
    #                                DistMultiVec, pow2-padded F)
    feat_dim: int = 0              # TRUE feature width (pad stripped)
    invdeg: object = None          # lazy col-aligned 1/deg DistVec (the
    #                                normalized-propagation twin; reset
    #                                on merge — degrees changed)
    headroom: float | None = None  # bucket-slot slack this version's
    #                                ELL builds reserved (merge state
    #                                must re-bucket with the same value)
    dyn: object = None             # dynamic.merge.MergeState (host
    #                                bucket structure for apply_delta)
    delta_from: tuple | None = None  # (parent vid, inserted keys,
    #                                removed keys) — refresh lineage
    vid: int = 0                   # assigned when installed/swapped in
    wal_seq: int = -1              # highest WAL sequence number folded
    #                                into this version (-1 = none) —
    #                                stamped into snapshot meta so
    #                                recovery replays exactly the
    #                                unapplied log suffix (round 16)

    def device_bytes(self) -> int:
        """Resident DEVICE bytes of this version: every uploaded array
        a plan's operands can come from (the ELL matrices and their
        twins, the feature table, the pagerank/dangling and lazy
        degree vectors, the CSC companion).  The multi-tenant pool's
        byte-accounted LRU evicts against this number
        (``serve.pool.resident_bytes``); host-side state (COO, degree
        tables, merge state) is deliberately NOT counted — eviction
        frees the device, the host retains the rebuild inputs."""
        total = 0
        for M in (self.E, self.E_weighted, self.P_ell, self.ET):
            if M is not None:
                total += sum(
                    int(a.nbytes) for b in M.buckets for a in b
                )
        for vec in (self.dangling, self.coldeg, self.invdeg, self.X):
            blocks = getattr(vec, "blocks", None)
            if blocks is not None:
                total += int(blocks.nbytes)
        if self.csc is not None:  # (indptr, rowidx) device pair
            total += sum(int(a.nbytes) for a in self.csc)
        return total


def _build_version(grid, rows, cols, nrows: int, ncols: int,
                   weights, kinds: tuple[str, ...], symmetric: bool,
                   keep_coo: bool, features=None,
                   headroom: float | None = None,
                   companion: bool = False,
                   companion_cap: int | None = None) -> GraphVersion:
    """Host-side construction of every artifact ``kinds`` need (the
    body of the old ``from_coo``): dedup the COO, build the structural
    / weighted / normalized / transposed matrices and the degree
    tables. Runs WITHOUT any engine lock — this is the double-buffered
    half of hot-swap: build the next generation while the current one
    keeps serving.  ``companion``: also the CSC companion of the deduped
    edges (an engine that serves BFS; a shard's slab does not), at the
    length ``companion_cap`` where they fit it (the one the engine's
    plans were traced with)."""
    from ..parallel.ellmat import EllParMat
    from ..parallel.vec import DistVec

    from ..tuner import config as tuner_config

    # resolve the env default NOW and store the concrete value: the
    # merge state must re-bucket with the slack the build ACTUALLY
    # used, not whatever COMBBLAS_DYNAMIC_HEADROOM says at merge time
    # (a changed env between build and merge would silently desync
    # orientation shapes from the retained device arrays)
    headroom = tuner_config.dynamic_headroom(headroom)
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    n = int(nrows)
    ncols = int(ncols)
    key = rows.astype(np.int64) * np.int64(ncols) + cols
    uniq, inv = np.unique(key, return_inverse=True)
    if weights is not None:
        w = np.full(len(uniq), np.inf, np.float32)
        np.minimum.at(w, inv, np.asarray(weights, np.float32))
        weights = w
    rows = (uniq // ncols).astype(rows.dtype)
    cols = (uniq % ncols).astype(cols.dtype)
    if "propagate" in kinds and ncols != n:
        # k-hop propagation chains ONE square operator; an explicit
        # kinds=("propagate",) on a rectangular graph would otherwise
        # die mid-trace at the second hop with a bare shape assert
        raise ValueError(
            f"'propagate' needs a square graph (nrows={n}, "
            f"ncols={ncols}): A^k is undefined on rectangles"
        )
    if ("bc" in kinds or "propagate" in kinds) and symmetric:
        # VERIFY the symmetry claim instead of trusting it: under
        # symmetric=True bc AND propagate reuse E as its own transpose,
        # and a forgotten symmetric=False would make every served score
        # silently wrong (bc's backward sweep would walk out-edges;
        # propagate's indicator hops would aggregate the wrong side)
        tkey = np.sort(
            cols.astype(np.int64) * np.int64(ncols) + rows
        )
        if ncols != n or not np.array_equal(uniq, tkey):
            raise ValueError(
                "symmetric=True but the COO is not structurally "
                "symmetric; pass symmetric=False (builds the "
                "transpose for bc) or symmetrize the graph"
            )
    def ell(r, c, v, nr, nc):
        # ``from_host_coo`` in its two halves, a span each
        with obs.span("bucket"):
            host = EllParMat.host_build(grid, r, c, v, nr, nc,
                                        headroom=headroom)
        with obs.span("upload") as upload:
            M = EllParMat.from_host_buckets(grid, host, nr, nc)
            upload.sync_on(M)
        return M

    with obs.span("serve.load", nrows=n, nnz=int(len(rows))):
        ones = np.ones(len(rows), np.float32)
        E = ell(rows, cols, ones, n, ncols)
        E_weighted = (
            ell(rows, cols, np.asarray(weights, np.float32), n, ncols)
            if weights is not None else None
        )
        # degree artifacts: rowdeg = in-edges per row; outdeg feeds
        # the pagerank normalization and the lazy coldeg_vec()
        # (device upload deferred until a plan consumes it)
        deg = np.bincount(rows, minlength=n).astype(np.int32)
        outdeg = np.bincount(cols, minlength=ncols).astype(np.int64)
        P_ell = dangling = None
        if "pagerank" in kinds:
            # column-stochastic normalization, host-side (the
            # reference's DimApply, PageRank.cpp:97-126)
            pvals = (
                1.0 / np.maximum(outdeg[cols], 1)
            ).astype(np.float32)
            P_ell = ell(rows, cols, pvals, n, ncols)
            dangling = DistVec.from_global(
                grid, (outdeg == 0).astype(np.float32), align="col"
            )
        ET = None
        if ("bc" in kinds or "propagate" in kinds) and not symmetric:
            ET = ell(cols, rows, ones, ncols, n)
        csc = None
        if companion:
            from ..parallel.ellmat import build_csc_companion

            with obs.span("companion") as comp:
                csc = build_csc_companion(
                    grid, rows, cols, n, ncols, headroom=headroom,
                    cap=companion_cap,
                )
                comp.sync_on(csc)
        X = None
        feat_dim = 0
        # like every other artifact here, the feature table is built
        # only when a served kind needs it: a features= arg whose
        # 'propagate' was excluded (rectangular default kinds,
        # explicit kinds=) must neither pay the [n, Fp] upload nor be
        # validated against a contract nothing will serve
        if features is not None and "propagate" in kinds:
            from ..parallel.spmm import pad_features
            from ..parallel.vec import DistMultiVec

            features = np.asarray(features, np.float32)
            if features.shape[0] != ncols:
                raise ValueError(
                    f"features rows {features.shape[0]} != graph "
                    f"column space {ncols} (one feature row per "
                    "vertex the hops aggregate from)"
                )
            feat_dim = int(features.shape[1])
            # pow2 pad: propagate plans compile per padded F, so two
            # versions inside one feature-width bucket share programs
            X = DistMultiVec.from_global(
                grid, pad_features(features), align="row"
            )
            obs.gauge("serve.propagate.feature_dim", feat_dim)
    return GraphVersion(
        nrows=n, ncols=ncols, nnz=int(len(rows)), E=E, deg=deg,
        outdeg=outdeg, E_weighted=E_weighted, P_ell=P_ell,
        dangling=dangling, ET=ET, csc=csc,
        host_coo=(rows, cols, ncols) if keep_coo else None,
        # the deduped (min-combined) weights ride along for the
        # mutation lane's merge-state bootstrap
        host_weights=weights if keep_coo else None,
        X=X, feat_dim=feat_dim, headroom=headroom,
    )


class GraphEngine:
    """One graph, loaded and query-ready. See module docstring.

    Build with ``GraphEngine.from_coo`` (host COO in the usual gather
    orientation: entry (i, j) means edge j -> i; symmetrize for
    undirected graphs). ``serve()`` wraps the engine in the batched,
    backpressured server (``combblas_tpu.serve.api.Server``).
    """

    @obs.spanned("serve.engine.init")
    def __init__(self, grid, E=None, *, nrows: int | None = None,
                 deg: np.ndarray | None = None,
                 E_weighted=None, P_ell=None, dangling=None, ET=None,
                 csc=None, coldeg=None, kinds: tuple[str, ...] | None = None,
                 pagerank_opts: tuple = (0.85, 1e-6, 100),
                 propagate_opts: tuple = (2, False),
                 max_iters: int | None = None,
                 version: GraphVersion | None = None):
        self.grid = grid
        if version is None:
            if E is None or nrows is None or deg is None:
                raise ValueError(
                    "GraphEngine needs either version= or E/nrows/deg"
                )
            version = GraphVersion(
                nrows=int(nrows),
                # read the real column count off E (a rectangular
                # engine's dedup keys and swap validation depend on it)
                ncols=int(getattr(E, "ncols", nrows)),
                nnz=-1,
                E=E, deg=np.asarray(deg),
                outdeg=None,
                E_weighted=E_weighted, P_ell=P_ell, dangling=dangling,
                ET=ET, csc=csc, coldeg=coldeg,
            )
        version.vid = 1
        self._version = version
        self.nrows = int(version.nrows)
        self.swaps = 0
        weighted_given = version.E_weighted is not None
        # kinds this engine was built to serve: only these get plans —
        # a kind whose artifacts were never built must be rejected at
        # the front door, not served with a silently-wrong stand-in
        # (no P_ell -> no pagerank; no weighted matrix -> no sssp, hop
        # counts are not distances; explicit kinds= opts back in)
        if kinds is None:
            kinds = tuple(
                k for k in KINDS
                if (k != "pagerank" or version.P_ell is not None)
                and (k != "sssp" or weighted_given)
                and (k != "propagate" or version.X is not None)
            )
        self._kinds = tuple(kinds)
        self.pagerank_opts = pagerank_opts
        self.propagate_opts = propagate_opts
        self.max_iters = max_iters
        self._plans: dict[tuple[str, int], _Plan] = {}
        # whole-graph analytics cache for refresh(): (kind, root) ->
        # {vid, result, niter} — the warm-restart recompute's memory
        self._analytics: dict = {}
        # refresh-mode history (cached/warm/cold counts): the
        # freshness surface (repair-vs-cold ratio) stats() reports and
        # dynamic.refresh emits as gauges
        self._refresh_modes: dict[str, int] = {}
        # ONE execution stream: plan building, warmup, and execute all
        # serialize here, so a caller-thread warmup() cannot race the
        # api worker's pump() on the plan cache (or the device)
        self._exec_lock = threading.RLock()
        # plan-cache DICT mutations/snapshots only — stats() must be
        # pollable during a long batch, so it must not touch _exec_lock
        self._plans_lock = threading.Lock()
        self.plan_hits = 0
        self.plan_misses = 0
        self._flags = None  # ``_push_operand``'s device bools

    # -- version delegation ------------------------------------------------
    # The loaded matrices live on the CURRENT GraphVersion; these
    # properties keep the pre-versioning attribute surface (engine.E,
    # engine.ET, ...) working while making every read swap-aware.

    @property
    def version(self) -> GraphVersion:
        return self._version

    @property
    def version_id(self) -> int:
        return self._version.vid

    @property
    def E(self):
        return self._version.E

    @property
    def deg(self):
        return self._version.deg

    @property
    def E_weighted(self):
        v = self._version
        return v.E_weighted if v.E_weighted is not None else v.E

    @property
    def P_ell(self):
        return self._version.P_ell

    @property
    def dangling(self):
        return self._version.dangling

    @property
    def ET(self):
        v = self._version
        return v.ET if v.ET is not None else v.E  # symmetric default

    @property
    def csc(self):
        return self._version.csc

    @csc.setter
    def csc(self, value):
        self._version.csc = value

    @property
    def coldeg(self):
        return self._version.coldeg

    @coldeg.setter
    def coldeg(self, value):
        self._version.coldeg = value

    @property
    def _outdeg(self):
        return self._version.outdeg

    @property
    def _host_coo(self):
        return self._version.host_coo

    @_host_coo.setter
    def _host_coo(self, value):
        self._version.host_coo = value

    # -- construction ------------------------------------------------------

    @staticmethod
    def from_coo(grid, rows, cols, nrows: int, ncols: int | None = None,
                 weights=None, kinds: tuple[str, ...] | None = None,
                 pagerank_alpha: float = 0.85, pagerank_tol: float = 1e-6,
                 pagerank_max_iters: int = 100,
                 max_iters: int | None = None,
                 symmetric: bool = True,
                 keep_coo: bool = False,
                 features=None,
                 propagate_hops: int = 2,
                 propagate_normalize: bool = False,
                 headroom: float | None = None) -> "GraphEngine":
        """Load a graph from host COO and build every derived artifact
        the requested ``kinds`` need (one host pass + one upload each —
        the kernel-1 role, amortized over the engine's whole lifetime).

        ``kinds`` defaults to every kind whose inputs were actually
        given: without ``weights``, 'sssp' is EXCLUDED (serving hop
        counts as "distances" would be a silent stand-in) — pass
        ``kinds`` naming it explicitly to serve unit-weight SSSP on a
        genuinely unweighted graph.

        The COO is DEDUPLICATED here (generators like
        ``rmat_symmetric_coo`` emit repeats, and a duplicate edge would
        silently act as weight-2 in BC's path counting); duplicate
        weighted edges keep the MINIMUM weight (the shortest-path
        natural combine, matching the reference's dedup-at-construction
        convention, ``SpParMat.from_global_coo dedup_sr=``).

        ``features`` ([n, F] host array) opts into the ``"propagate"``
        kind: lane w of a propagate batch returns the k-hop propagated
        feature row of vertex w (``propagate_hops`` hops;
        ``propagate_normalize=True`` serves the degree-normalized
        smoothing ``(D⁻¹A)ᵏX``).  ``headroom`` reserves a slack
        fraction of padding slots per ELL bucket class at build
        (``COMBBLAS_DYNAMIC_HEADROOM``) so the dynamic mutation lane
        re-buckets growing rows instead of spilling to a rebuild.
        """
        ncols = nrows if ncols is None else int(ncols)
        n = int(nrows)
        if kinds is None:
            kinds = tuple(
                k for k in KINDS
                if (k != "sssp" or weights is not None)
                and (k != "bc" or ncols == n)  # bc needs a square graph
                # propagate chains hops through one square operator —
                # a rectangular graph has no A^k to serve
                and (k != "propagate"
                     or (features is not None and ncols == n))
            )
        version = _build_version(
            grid, rows, cols, n, ncols, weights, tuple(kinds),
            symmetric, keep_coo, features=features, headroom=headroom,
            companion="bfs" in kinds,
        )
        return GraphEngine(
            grid, version=version, kinds=tuple(kinds),
            pagerank_opts=(pagerank_alpha, pagerank_tol,
                           pagerank_max_iters),
            propagate_opts=(int(propagate_hops),
                            bool(propagate_normalize)),
            max_iters=max_iters,
        )

    # -- graph versions / hot-swap -----------------------------------------

    def build_version(self, rows, cols, weights=None,
                      ncols: int | None = None, symmetric: bool = True,
                      keep_coo: bool = False,
                      features=None) -> GraphVersion:
        """Build the NEXT graph generation for this engine — same
        nrows, same kinds — entirely outside the execution lock (the
        double-buffered half of hot-swap: current version keeps
        serving while this one is constructed host-side + uploaded).
        Hand the result to ``swap()`` (or ``Server.swap_graph``)."""
        t0 = time.perf_counter()
        v = _build_version(
            self.grid, rows, cols, self.nrows,
            # default to the CURRENT version's ncols (not nrows): a
            # rectangular engine's dedup key and index split are
            # ncols-based, and a silently-wrong ncols would merge
            # distinct edges
            self._version.ncols if ncols is None else int(ncols),
            weights, self._kinds, symmetric, keep_coo,
            features=features,
            # bucket shapes must round-trip the swap: reuse the
            # engine's configured headroom
            headroom=self._version.headroom,
            companion="bfs" in self._kinds,
            # like the buckets' shapes, the companion's rides the swap
            companion_cap=(
                None if self._version.csc is None
                else int(self._version.csc[1].shape[-1])
            ),
        )
        if v.X is None and self._version.X is not None:
            # features are edge-independent: a version rebuilt without
            # an explicit new table KEEPS the served one (same device
            # arrays — no re-upload, no retrace)
            v.X = self._version.X
            v.feat_dim = self._version.feat_dim
        obs.observe("serve.swap.build_s", time.perf_counter() - t0)
        return v

    def apply_delta(self, batch, **kw) -> GraphVersion:
        """Build the NEXT version by merging a delta batch into the
        CURRENT one (``combblas_tpu.dynamic.merge.apply_delta`` — per
        tile, slot-capacity-aware, spill-to-rebuild; see
        docs/dynamic.md).  Like ``build_version`` this runs entirely
        OUTSIDE the execution lock and the current version keeps
        serving; hand the result to ``swap()`` / ``Server.swap_graph``
        — an incremental merge preserves every operand shape, so the
        swap keeps the zero-retrace guarantee.  Requires the host edge
        list (``from_coo(..., keep_coo=True)``)."""
        from ..dynamic import merge as dyn_merge

        t0 = time.perf_counter()
        v = dyn_merge.apply_delta(
            self._version, batch, kinds=self._kinds, **kw
        )
        obs.observe("serve.swap.build_s", time.perf_counter() - t0)
        return v

    def refresh(self, kind: str, root: int | None = None,
                force_cold: bool = False) -> dict:
        """Whole-graph analytic with warm-restart recompute
        (``dynamic.refresh``): BFS levels from ``root``, CC labels, or
        the global PageRank vector — repaired from the engine's cached
        previous result when the current version's delta lineage allows
        it (insert-only for bfs/cc; always for pagerank), recomputed
        cold otherwise.  Returns ``{"result", "niter", "mode"
        (cached/warm/cold), "vid", ...}`` with host numpy results.
        Serialized on the execution lock like every device access."""
        from ..dynamic.refresh import refresh_analytic

        with self._exec_lock:
            return refresh_analytic(
                self, kind, root=root, force_cold=force_cold
            )

    def swap(self, version: GraphVersion) -> float:
        """Atomically install ``version`` as the current graph. Blocks
        on the execution lock, so the in-flight batch (if any) finishes
        on the OLD version; every later execute reads the new one. The
        plan cache is untouched — plans take the matrices as call-time
        arguments, so same-shape versions re-use every compiled
        executable (zero retraces; a different-shape version retraces
        once per plan, visibly). Returns the swap latency in seconds
        (lock wait + pointer flip), also an obs histogram
        (``serve.swap.latency_s``)."""
        if not isinstance(version, GraphVersion):
            raise TypeError(
                f"swap() takes a GraphVersion (see build_version), "
                f"got {type(version).__name__}"
            )
        if int(version.nrows) != self.nrows:
            # results are [nrows, W]: changing nrows breaks every
            # queued caller's contract — that is a new engine, not a
            # version swap
            raise ValueError(
                f"version nrows={version.nrows} != engine nrows="
                f"{self.nrows}; hot-swap preserves the result shape"
            )
        if int(version.ncols) != int(self._version.ncols):
            raise ValueError(
                f"version ncols={version.ncols} != engine ncols="
                f"{self._version.ncols}; a different column space is "
                "a new engine, not a version swap"
            )
        if "pagerank" in self._kinds and version.P_ell is None:
            raise ValueError(
                "engine serves 'pagerank' but the new version has no "
                "P_ell; build it via engine.build_version(...)"
            )
        if "propagate" in self._kinds and version.X is None:
            raise ValueError(
                "engine serves 'propagate' but the new version has no "
                "feature table; pass features= to build_version (or "
                "reuse the current one via engine.build_version)"
            )
        if (
            "sssp" in self._kinds
            and self._version.E_weighted is not None
            and version.E_weighted is None
        ):
            # a weighted engine must stay weighted: the E_weighted
            # property would silently fall back to the structural E
            # and serve hop counts as distances (an engine built
            # unit-weight by explicit kinds= opt-in stays consistent)
            raise ValueError(
                "engine serves weighted 'sssp' but the new version "
                "has no weights; pass weights= to build_version"
            )
        t0 = time.perf_counter()
        with self._exec_lock:
            version.vid = self._version.vid + 1
            self._version = version
            self.swaps += 1
        dt = time.perf_counter() - t0
        obs.observe("serve.swap.latency_s", dt)
        obs.gauge("serve.graph.version", version.vid)
        obs.count("serve.swap.count")
        return dt

    def coldeg_vec(self):
        """Col-aligned out-degree DistVec (the budget input of the
        direction-optimized kernels) — built lazily: no served plan
        consumes it, so the device upload is deferred to first use and
        cached."""
        if self.coldeg is None:
            outdeg = getattr(self, "_outdeg", None)
            if outdeg is None:
                raise ValueError(
                    "coldeg_vec needs the degree table: build the "
                    "engine with GraphEngine.from_coo"
                )
            from ..parallel.vec import DistVec

            self.coldeg = DistVec.from_global(
                self.grid, outdeg.astype(np.int32), align="col"
            )
        return self.coldeg

    def csc_companion(self, grow: bool = True):
        """The CSC companion of the served graph
        (``ellmat.build_csc_companion``): the ``(indptr, rowidx)``
        device pair whose columns a thin level of the BFS plan walks
        from its frontier (``models.bfs._bfs_batch_tallied``).
        ``from_coo`` builds it with the matrices when ``"bfs"`` is
        served, snapshots carry it, and this returns it.

        Where the version's is not current (a structural merge keeps
        the parent's arrays as a stand-in of the same shapes; a
        snapshot from before there was one has a placeholder) it is
        rebuilt here from the version's host COO, off the query path:
        the sort and the upload hold no lock, the flip is one
        assignment under the execution lock.  The length the plans were
        traced with is kept when the edges fit it (zero retraces), and
        outgrown by the ``headroom`` policy when they do not (one
        retrace a width, like any version of another shape), unless
        ``grow`` is False: then the stand-in stays and None is
        returned (the write lane's call: never a retrace on the query
        path).  Needs the edge list: ``from_coo(..., keep_coo=True)``,
        which every merged version has."""
        v = self._version
        if v.csc is not None and v.csc_current:
            return v.csc
        if v.host_coo is None:
            raise ValueError(
                "csc_companion needs the host COO to rebuild from: "
                "build the engine with GraphEngine.from_coo("
                "keep_coo=True)"
            )
        from ..parallel.ellmat import (
            build_csc_companion_host, upload_csc_companion)

        rows, cols, ncols = v.host_coo
        cap = None if v.csc is None else int(v.csc[1].shape[-1])
        if not grow and cap is not None:
            # one count a tile, before the sort that would be thrown away
            g = self.grid
            tile = (np.asarray(rows) // g.local_rows(self.nrows)) * g.pc \
                + np.asarray(cols) // g.local_cols(ncols)
            if np.bincount(tile, minlength=g.size).max() > cap:
                return None
        indptr, rowidx = build_csc_companion_host(
            self.grid, rows, cols, self.nrows, ncols,
            headroom=v.headroom, cap=cap,
        )
        csc = upload_csc_companion(self.grid, indptr, rowidx)
        with self._exec_lock:
            v.csc, v.csc_current = csc, True
        return csc

    def _push_operand(self) -> tuple:
        """``(indptr, rowidx, current)`` for the BFS plan.  A version
        without a companion (built by hand, or restored from a snapshot
        that predates it) gets the smallest stand-in, marked
        not-current: its batches sweep every level."""
        import jax.numpy as jnp

        v = self._version
        if v.csc is None:
            from ..parallel.ellmat import upload_csc_companion

            g, lc = self.grid, self.grid.local_cols(v.ncols)
            v.csc = upload_csc_companion(
                g, np.zeros((g.pr, g.pc, lc + 1), np.int32),
                np.full((g.pr, g.pc, 1), g.local_rows(v.nrows), np.int32),
            )
            v.csc_current = False
        if self._flags is None:
            # two device scalars for the engine's life: no transfer a
            # batch, and a swap between current and not is no retrace
            self._flags = {b: jnp.asarray(b) for b in (False, True)}
        return (*v.csc, self._flags[bool(v.csc_current)])

    def serve(self, config=None, tenant: str | None = None):
        from .api import Server
        from .scheduler import ServeConfig

        return Server(self, config or ServeConfig(), tenant=tenant)

    # -- plan cache --------------------------------------------------------

    def kinds(self) -> tuple[str, ...]:
        """The kinds this engine was BUILT to serve — a kind outside
        this set is rejected (its artifacts may not exist: e.g. ET for
        BC on a directed graph), never served with a stand-in."""
        return self._kinds

    def plan(self, kind: str, width: int) -> _Plan:
        """The warm executable for (kind, width) — built (a cache MISS,
        which traces and possibly compiles) only on first use; warm it
        via ``warmup()`` so serving never misses."""
        if kind not in self._kinds:
            raise ValueError(
                f"engine was not built for kind {kind!r} "
                f"(kinds={self._kinds})"
            )
        key = (kind, int(width))
        with self._exec_lock:
            with self._plans_lock:
                p = self._plans.get(key)
            if p is not None:
                self.plan_hits += 1
                obs.count("serve.plan_cache.hits", kind=kind, width=width)
                return p
            self.plan_misses += 1
            obs.count("serve.plan_cache.misses", kind=kind, width=width)
            p = self._build_plan(kind, int(width))
            with self._plans_lock:
                self._plans[key] = p
            return p

    def _build_plan(self, kind: str, width: int) -> _Plan:
        import jax

        from ..models.bc import _bc_batch_lanes
        from ..models.bfs import _bfs_batch_tallied
        from ..models.pagerank import _pagerank_batch_impl
        from ..models.sssp import _sssp_batch_impl

        plan = _Plan(kind=kind, width=width, fn=None)

        def trace_mark():
            # runs at TRACE time only: counts (re)traces, not executions
            plan.traces += 1
            obs.count("trace.serve", kind=kind, width=width)

        if kind == "bfs":

            def impl(E, csc, sources):
                # (parents, levels, niter, sweep tally, what the pushes
                # did): the last two are read back only with telemetry
                # on (``execute``)
                trace_mark()
                return _bfs_batch_tallied(
                    E, sources, self.max_iters, True, csc
                )

        elif kind == "sssp":

            def impl(E, sources):
                # (dist, parents, rounds, the rounds' class sweeps by
                # tile, class and mode)
                trace_mark()
                return _sssp_batch_impl(E, sources)

        elif kind == "pagerank":
            if self.P_ell is None:
                raise ValueError(
                    "engine was built without the pagerank artifacts "
                    "(kinds= did not include 'pagerank')"
                )
            alpha, tol, iters = self.pagerank_opts

            def impl(P, dangling, sources):
                trace_mark()
                return _pagerank_batch_impl(
                    P, sources, dangling, alpha=alpha, tol=tol,
                    max_iters=iters,
                )

        elif kind == "bc":

            def impl(E, ET, sources):
                # (per-lane dependencies, levels of the deepest lane,
                # sweeps by BC_PHASES, class sweeps by phase, tile,
                # class and mode)
                trace_mark()
                return _bc_batch_lanes(E, ET, sources, self.max_iters)

        elif kind == "propagate":
            from ..models.propagate import _propagate_batch_impl

            if self._version.X is None:
                raise ValueError(
                    "engine was built without a feature table "
                    "(from_coo(features=...) opts into 'propagate')"
                )
            hops, normalize = self.propagate_opts
            backend = self._resolve_spmm_backend()

            def impl(ET, X, invdeg, sources):
                trace_mark()
                return _propagate_batch_impl(
                    ET, X, invdeg, sources, hops=hops,
                    normalize=normalize, backend=backend,
                )

        else:
            raise ValueError(f"unknown query kind {kind!r}")

        # the program's name in the compiler's text, the profiler's
        # ``XLA Modules`` line and the persistent-cache key: what it
        # is, not ``impl`` for every plan
        impl.__name__ = impl.__qualname__ = f"serve_{kind}_w{width}"
        jitted = jax.jit(impl)
        # operands resolved at CALL time from the current GraphVersion
        # (not closed over): this is what lets swap() replace the graph
        # under a surviving plan cache — same-shape operands hit the
        # jit signature cache, different shapes retrace exactly once
        plan.fn = lambda sources: jitted(*self._plan_args(kind), sources)
        plan.lower = lambda sources: jitted.lower(
            *self._plan_args(kind), sources
        )
        return plan

    def _publish_op_names(self, plan: "_Plan", sources) -> None:
        """Telemetry on, at warm-up: the compiled program's
        ``{instruction: op_name}`` table (``obs.opnames``), so a device
        trace's operations can be laid under the named scopes.  The
        program is already traced and compiled: lowering it again hits
        JAX's trace cache (no retrace is counted) and compiling it
        fetches it from the persistent cache where one is kept."""
        obs.opnames.publish(
            lambda: plan.lower(sources).compile().as_text()
        )

    def _resolve_spmm_backend(self) -> str:
        """The propagate plans' SpMM backend: a static closure constant
        of every such plan."""
        from ..parallel.spmm import resolve_spmm_backend
        from ..semiring import PLUS_TIMES

        return resolve_spmm_backend(PLUS_TIMES)

    def _propagate_invdeg(self):
        """Col-aligned 1/deg DistVec for normalized propagation — lazy
        per version (a merge resets it: degrees changed)."""
        v = self._version
        if v.invdeg is None:
            from ..parallel.vec import DistVec

            v.invdeg = DistVec.from_global(
                self.grid,
                (1.0 / np.maximum(v.deg, 1)).astype(np.float32),
                align="col",
            )
        return v.invdeg

    def _plan_args(self, kind: str) -> tuple:
        """The current version's operands for one kind (the properties
        apply the unit-weight / symmetric-transpose fallbacks)."""
        if kind == "bfs":
            return (self.E, self._push_operand())
        if kind == "sssp":
            return (self.E_weighted,)
        if kind == "pagerank":
            return (self.P_ell, self.dangling)
        if kind == "propagate":
            _hops, normalize = self.propagate_opts
            return (
                self.ET, self._version.X,
                self._propagate_invdeg() if normalize else None,
            )
        return (self.E, self.ET)

    #: Lane widths every warmup covers (the batcher's pow2 buckets).
    DEFAULT_WARMUP_WIDTHS = (1, 2, 4, 8, 16)

    def warmup(self, kinds: tuple[str, ...] | None = None,
               widths: tuple[int, ...] | None = None) -> dict:
        """Pre-trace/compile every (kind, width) plan by executing it
        once on an all-``PAD_ROOT`` batch (inert lanes: the program
        shape is identical, the search trivially empty) and blocking.
        After this, serving a request mix that stays inside ``kinds`` x
        ``widths`` performs ZERO traces — assert via
        ``retraces_since(mark)`` or the ``trace.serve`` obs counter.
        Returns {(kind, width): seconds}.

        ``widths=None`` (default) warms ``DEFAULT_WARMUP_WIDTHS``;
        explicit ``widths`` warms exactly those.  A ``Server`` always
        passes its config's ``lane_widths`` (``Server.warmup``): the
        widths its batcher can form.
        """
        import jax

        from ..models.bfs import FRONTIER_PAYLOAD, frontier_table_bytes

        kinds = self.kinds() if kinds is None else kinds
        widths = self.DEFAULT_WARMUP_WIDTHS if widths is None else widths
        if "bfs" in kinds and self._version.host_coo is not None:
            # a companion that is not current is rebuilt BEFORE the
            # plans are traced with a stand-in's shapes
            with obs.span("serve.warmup.companion"):
                self.csc_companion()
        out = {}
        for kind in kinds:
            for w in sorted(set(widths)):
                t0 = time.perf_counter()
                # parts: ``build`` (the plan object), ``execute`` (trace,
                # lower, fetch or compile, and the first run: JAX's own
                # seconds for the first four land here as span events),
                # ``probe`` (telemetry's own cost, traced boots only)
                with self._exec_lock, obs.span(
                    "serve.warmup", kind=kind, width=int(w)
                ) as sp:
                    plan = self.plan(kind, w)
                    if kind == "bfs":
                        # what a level's sweep gathers from, a tile
                        sp.annotate(
                            payload=FRONTIER_PAYLOAD,
                            table_bytes=frontier_table_bytes(self.E, w),
                        )
                    sp.mark("build")
                    pads = np.full(int(w), PAD_ROOT, np.int32)
                    jax.block_until_ready(plan.fn(pads))
                    sp.mark("execute")
                    if obs.ENABLED:
                        self._publish_op_names(plan, pads)
                        sp.mark("probe")
                out[(kind, int(w))] = time.perf_counter() - t0
        return out

    def trace_mark(self) -> int:
        """Total traces across all plans (snapshot before serving, then
        ``retraces_since`` asserts the zero-retrace contract)."""
        return sum(p.traces for p in self._plans.values())

    def retraces_since(self, mark: int) -> int:
        return self.trace_mark() - mark

    # -- execution ---------------------------------------------------------

    def _lanes_to_global(self, blocks) -> np.ndarray:
        """[pa, L, W] blocks (already on the host) -> [n, W] — via
        ``DistMultiVec.to_global`` so the block-layout knowledge stays
        in exactly one place."""
        from ..parallel.vec import DistMultiVec

        return DistMultiVec(
            blocks=blocks, length=self.nrows, align="row", grid=self.grid
        ).to_global()

    #: Result names of each kind's device blocks, in program order.  An
    #: iteration count follows them (BFS levels, Bellman-Ford rounds,
    #: PageRank iterations, and for "bc" the BFS levels of the batch's
    #: deepest lane), then what the kind's program counted of itself,
    #: read only with telemetry on ("bfs": the levels' class sweeps and
    #: the push's outcome; "sssp": the rounds' class sweeps; "bc": sweeps
    #: by phase, and both loops' class sweeps; the tally of class sweeps
    #: is output ``_TALLY_AT`` of all three).
    _RESULT_KEYS = {
        "bfs": ("parents", "levels"),
        "sssp": ("dist", "parents"),
        "pagerank": ("ranks",),
        "bc": ("scores",),
    }

    def execute(self, kind: str, sources, parts: list | None = None
                ) -> dict:
        """Run one batch: ``sources`` is the int32 lane vector (pad
        slots = ``PAD_ROOT``). Returns a dict of host arrays with the
        lane axis LAST (what ``batcher.scatter`` slices per request).
        It is ``collect(launch(...))`` under one hold of the execution
        lock; the serving worker calls the halves itself, with the next
        batch's ``launch`` between ``wait`` and ``collect``.

        ``parts``: a list the caller owns (the worker passes one when a
        member of the batch is traced; engines are shared, so nothing is
        kept here).  With telemetry on, the boundaries of the work are
        appended to it as ``(part, perf_counter at its end)`` for
        ``launch`` (``jnp.asarray`` + dispatch until the plan returns),
        ``device`` (``block_until_ready``: only a traced batch waits
        apart from its readback), ``readback`` (``np.asarray`` of every
        result block) and ``to_global`` (reshape, slice, ``int(niter)``),
        each also a ``serve.execute.<part>`` annotation on the
        profiler's clock.  Without ``parts``, or with telemetry off, no
        clock is read and nothing waits before the readback.
        """
        with self._exec_lock, obs.span(
            "serve.batch", kind=kind, width=len(sources)
        ):
            return self._collect(self._launch(kind, sources, parts))

    def launch(self, kind: str, sources, parts: list | None = None
               ) -> "_Launched":
        """The first half of ``execute``: dispatch the plan and return
        at once with its un-read device results.  The lock is held for
        the dispatch alone, so the order of programs is the same on
        every chip of the mesh whoever launches next."""
        with self._exec_lock, obs.span(
            "serve.batch", kind=kind, width=len(sources)
        ):
            return self._launch(kind, sources, parts)

    def wait(self, handle: "_Launched") -> None:
        """Block until the device work of a launched batch has ended and
        start its results on their way to the host (the part ``device``
        where the batch is traced).  Holds no lock: a swap or another
        launch may go ahead meanwhile."""
        import jax

        with handle.mark("device"):
            jax.block_until_ready(handle.res)
            # queued ahead of whatever is launched next; ``collect``'s
            # ``np.asarray`` then finds the copy under way (20 ms of a
            # mesh batch's 867 ms readback, 6 of 41 on one chip)
            for out in jax.tree_util.tree_leaves(handle.res):
                out.copy_to_host_async()
        handle.waited = True

    def collect(self, handle: "_Launched") -> dict:
        """The second half of ``execute``: read a launched batch back
        and lay it out ``[n, W]``; byte and sweep counters included."""
        with self._exec_lock:
            return self._collect(handle)

    def _launch(self, kind: str, sources, parts) -> "_Launched":
        import jax.numpy as jnp

        sources = np.asarray(sources, np.int32)
        W = sources.shape[0]
        plan = self.plan(kind, W)
        traced = parts is not None and obs.ENABLED
        mark = _PartClock(parts) if traced else _no_clock
        with mark("launch"):
            res = plan.fn(jnp.asarray(sources))
            plan.executions += 1
        swept = self._swept(kind) if obs.ENABLED and kind in _TALLIED else ()
        return _Launched(
            kind, W, res, mark, self._version.feat_dim, swept)

    def _swept(self, kind: str) -> tuple:
        """What weighs a batch's tally of class sweeps on the host: for
        each loop of the kind's program that tallies, in the tally's
        order, its labels in the ELL family and the
        ``ellmat.class_slots`` of the matrix it sweeps, of the CURRENT
        version (plans outlive a swap; a batch's weights are those of
        the version it was launched on, as ``feat_dim`` is)."""
        from ..models.bc import BC_PHASES
        from ..parallel.ellmat import class_slots

        if kind == "bc":
            return tuple(
                ({"phase": phase}, class_slots(M))
                for phase, M in zip(BC_PHASES, (self.E, self.ET)))
        return (({}, class_slots(self._plan_args(kind)[0])),)

    def _collect(self, handle: "_Launched") -> dict:
        kind, W, res, mark = handle.kind, handle.width, handle.res, handle.mark
        if mark is not _no_clock and not handle.waited:
            self.wait(handle)  # a traced batch waits apart from its readback
        if kind == "propagate":
            # [Fp, W] replicated features — strip the pow2 pad
            # lanes back to the true feature dim; lane axis stays
            # LAST (the batcher's scatter contract)
            from ..parallel.spgemm import host_value

            with mark("readback"):
                feats = host_value(res)
            with mark("to_global"):
                return {"features": feats[: handle.feat_dim]}
        keys = self._RESULT_KEYS[kind]
        # "batch_niter" is BATCH metadata (the max iteration count
        # over all lanes, pad included), not a per-request fact: a
        # request's own value would vary with its batch-mates
        blocks, niter = res[:len(keys)], res[len(keys)]
        counted = res[len(keys) + 1:]
        with mark("readback"):
            host = [np.asarray(b) for b in blocks]
        if obs.ENABLED:
            obs.count(
                "serve.readback.bytes",
                sum(h.nbytes for h in host), kind=kind, width=W,
            )
            # (a few bytes a batch, not part of the result: kept out
            # of the byte counter above)
            if handle.swept:
                handle.work = self._count_sweeps(
                    kind, W, res[_TALLY_AT], handle.swept)
            if kind == "bfs":
                handle.work = dict(
                    handle.work or {},
                    **self._count_levels(W, int(niter), counted[-1]))
            if kind == "sssp":
                obs.count("serve.sssp.rounds", int(niter), width=W)
                obs.count("serve.sssp.batches", 1, width=W)
            if kind == "bc":
                from ..models.bc import BC_PHASES

                for phase, ran in zip(BC_PHASES, np.asarray(counted[0])):
                    obs.count("serve.bc.sweeps", int(ran),
                              phase=phase, width=W)
                obs.count("serve.bc.batches", 1, width=W)
        with mark("to_global"):
            out = {
                k: self._lanes_to_global(h) for k, h in zip(keys, host)
            }
            out["batch_niter"] = int(niter)
            return out

    @staticmethod
    def _count_levels(width: int, niter: int, report) -> dict:
        """A BFS batch's ``models.bfs.PushReport`` read back and added to
        ``serve.bfs.push{outcome}`` (level 0's), ``serve.bfs.levels
        {mode, width}`` (the batch's levels by how each was run:
        ``niter`` in all), ``serve.bfs.push_edges{width}`` (the edges
        its pushes walked, all tiles) and ``serve.bfs.push_passes{width}``
        (the passes over a trip of ``ellmat.PUSH_SLOT_CHUNK`` slots that
        scattered them).  Returns the same for the batch's stage
        record."""
        from ..models.bfs import LEVEL_MODES, PUSH_OUTCOMES

        pushed = int(report.levels)
        edges, passes = (int(np.asarray(tiles, np.int64).sum())
                         for tiles in (report.edges, report.passes))
        obs.count(
            "serve.bfs.push", outcome=PUSH_OUTCOMES[int(report.outcome)])
        for mode, ran in zip(LEVEL_MODES, (pushed, niter - pushed)):
            obs.count("serve.bfs.levels", ran, mode=mode, width=width)
        obs.count("serve.bfs.push_edges", edges, width=width)
        obs.count("serve.bfs.push_passes", passes, width=width)
        return {"levels": niter, "push_levels": pushed, "push_edges": edges,
                "push_passes": passes}

    @staticmethod
    def _count_sweeps(kind: str, width: int, tally, swept: tuple) -> dict:
        """A batch's tally of class sweeps (``int32[pr, pc, classes, 2]``;
        "bc" alone has two loops, one a phase in front) read back and
        added, the one way for every kind, to ``ell.class_sweeps`` /
        ``ell.slots`` / ``ell.batches`` (``ellmat.count_sweep_work``,
        once a loop of ``swept``).  Returns the batch's own work for its
        stage record (``_Launched.work``), its loops added up: the slots
        the busiest tile gathered and skipped."""
        from ..parallel.ellmat import count_sweep_work

        tally = np.asarray(tally)
        slots = sum(
            count_sweep_work(kind, width, counts, weights, **labels)
            for (labels, weights), counts in zip(
                swept, tally if kind == "bc" else tally[None]))
        obs.count("ell.batches", 1, kind=kind, width=width)
        return {"slots": int(slots[0]), "slots_skipped": int(slots[1])}

    def stats(self) -> dict:
        # _plans_lock only: polling stats during a long batch must not
        # block on the device-holding execution lock
        with self._plans_lock:
            plans = {
                f"{k}/{w}": {
                    "traces": p.traces, "executions": p.executions,
                }
                for (k, w), p in sorted(self._plans.items())
            }
            hits, misses = self.plan_hits, self.plan_misses
        warm = self._refresh_modes.get("warm", 0)
        cold = self._refresh_modes.get("cold", 0)
        vid = self._version.vid
        return {
            "plans": plans,
            "plan_hits": hits,
            "plan_misses": misses,
            "nrows": self.nrows,
            "kinds": list(self.kinds()),
            "graph_version": vid,
            "graph_nnz": self._version.nnz,
            "swaps": self.swaps,
            # dynamic-lane freshness (round 15): how stale the cached
            # analytics are vs the served version, and how often a
            # refresh repaired instead of recomputing cold
            "freshness": {
                "refresh_modes": dict(self._refresh_modes),
                "repair_ratio": (
                    warm / (warm + cold) if warm + cold else None
                ),
                "versions_behind": (
                    max(
                        (vid - e["vid"] for e in self._analytics.values()),
                        default=0,
                    )
                ),
            },
        }
