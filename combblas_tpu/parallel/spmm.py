"""SpMM — sparse matrix × dense feature block, the MXU-resident lane.

Every serving kind before round 12 was VECTOR-valued (BFS / SSSP /
PageRank / BC lanes over [n, W] frontier matrices); the one shape the
MXU is actually built for — a sparse adjacency times a dense feature
panel — had no first-class kernel.  This module is that kernel family,
the graph-ML workload lane (k-hop feature propagation, embedding
smoothing) the ROADMAP names:

* ``_ell_local_spmm`` — per degree-class bucket, gather the neighbor
  FEATURE ROWS (``[nb, kb, F]`` — one gathered index fetches F lanes,
  the same per-index-bound amortization the batched BFS kernels ride)
  and contract the k axis.  Backend ``"mxu_gather"`` (plus_times only)
  contracts with a batched ``dot_general`` — a [1, kb] × [kb, F] matmul
  per bucket row, MXU-eligible; backend ``"scatter"`` is the
  VPU fold + row scatter of ``_ell_local_spmv_multi``, exact for every
  semiring (min_plus, max_min, ... ride ``_bucket_fold`` +
  ``_scatter_rows``'s duplicate-safe combine).

* ``dist_spmm_ell`` — the distributed entry over the EllParMat
  schedule: the feature panel replicates down grid columns, each tile
  folds locally, results reduce over the "c" axis.  O(lc·F) panel
  memory per device; the right shape when F is modest (serve lanes).

* ``summa_spmm`` — SUMMA over SpParMat tiles × a ``DenseParMat``
  feature panel (F split over grid columns like B's columns in
  SpGEMM).  ``ring=True`` reuses the round-9 carousel machinery
  (``_carousel_perms`` / ``_rotate_tiles``, two-slot operand buffers):
  the dense panel ROTATES one neighbor per stage while the current
  stage contracts, and with ``pipeline=True`` stage ``s+1``'s
  ``ppermute`` is issued before stage ``s``'s accumulate — O(2·panel)
  peak memory instead of the gathered schedule's O(p·panel).

* ``spmm_khop`` — fused k-hop propagation: hops chain DEVICE-RESIDENT
  (no host round-trip between hops), optional per-hop row
  normalization (``Y ← D⁻¹(A·Y)`` — value-identical to multiplying by
  the row-normalized twin the PageRank lane builds, derived here from
  the row degrees instead of materializing a second matrix).

Backend routing: a vetted ``backend`` argument, else
``spmm_backend_heuristic(sr)`` — see ``resolve_spmm_backend``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import obs
from ..semiring import PLUS_TIMES, Semiring
from .collectives import axis_reduce
from .grid import COL_AXIS, ROW_AXIS
from .dense import DenseParMat
from .ellmat import EllParMat, _ell_local_spmm
from .spmat import SpParMat, TILE_SPEC
from .vec import DistMultiVec, DistVec

Array = jax.Array

#: The SpMM backend ladder (also the tuner's op="spmm" tier names).
SPMM_BACKENDS = ("mxu_gather", "scatter")


def pad_feature_width(f: int) -> int:
    """Pow2-padded feature width: SpMM programs compile per (shape,
    F) signature, so bucketing F to powers of two bounds the compiled
    program count exactly like the serve batcher's lane buckets bound
    the (kind, W) plans."""
    return 1 << max(int(f) - 1, 0).bit_length()


def pad_features(x, width: int | None = None) -> np.ndarray:
    """Host [n, F] → [n, pad_feature_width(F)] float32, zero-filled
    pad lanes.  Feature columns are INDEPENDENT through every kernel
    (no cross-lane fold), so pad lanes can never contaminate the real
    F lanes; the pad lanes themselves stay zero only under plus_times
    (0 is its semiring zero) — under min_plus/max_min they carry the
    fold of an all-zero input column, so consumers must slice back to
    the true F (spmm_khop callers and the serve lane do)."""
    x = np.asarray(x, np.float32)
    if x.ndim != 2:
        raise ValueError(f"features must be [n, F], got shape {x.shape}")
    fp = pad_feature_width(x.shape[1]) if width is None else int(width)
    if fp < x.shape[1]:
        raise ValueError(f"pad width {fp} < feature dim {x.shape[1]}")
    out = np.zeros((x.shape[0], fp), np.float32)
    out[:, : x.shape[1]] = x
    return out


def spmm_backend_heuristic(sr: Semiring) -> str:
    """The no-measurement fallback: plus_times contracts on the MXU,
    everything else folds on the VPU (the dense dot IS the plus_times
    contraction — there is no dot-shaped min_plus on this hardware
    short of a Pallas kernel)."""
    return "mxu_gather" if sr.name == "plus_times" else "scatter"


def admissible_spmm_backends(sr: Semiring) -> tuple[str, ...]:
    """Backends that produce exact results for ``sr``: what
    ``resolve_spmm_backend`` vets an argument against."""
    if sr.name == "plus_times":
        return ("mxu_gather", "scatter")
    return ("scatter",)


# -- distributed ELL entry ---------------------------------------------------
# (the LOCAL gather-contract kernel `_ell_local_spmm` lives in
# ellmat.py next to the format — the batched SpMV lanes share it as
# their scatter backend)


@partial(jax.jit, static_argnames=("sr", "backend"))
def dist_spmm_ell(
    sr: Semiring, E: EllParMat, X: DistMultiVec, backend: str = "scatter"
) -> DistMultiVec:
    """Y = E ⊗ X for a dense feature block X ([n, F] DistMultiVec) —
    the EllParMat schedule (panel replicated down grid columns, fold
    over the "c" axis), local kernel per ``backend``."""
    assert backend in SPMM_BACKENDS, backend
    assert X.length == E.ncols
    if obs.ENABLED:
        # trace-time: counts (re)traces per static config, the same
        # retrace-visibility convention as trace.summa_spgemm
        obs.count("trace.spmm_ell", backend=backend, sr=sr.name)
    X = X.realign("col")
    lr, lc = E.local_rows, E.local_cols
    nb = len(E.buckets)

    def body(xblk, *flat):
        buckets = [
            tuple(a[0, 0] for a in flat[3 * i : 3 * i + 3]) for i in range(nb)
        ]
        y, _ = _ell_local_spmm(sr, buckets, xblk[0], lr, lc, backend)
        return axis_reduce(sr, y, COL_AXIS)[None]

    flat_args = [a for b in E.buckets for a in b]
    blocks = jax.shard_map(
        body,
        mesh=E.grid.mesh,
        in_specs=(P(COL_AXIS),) + (TILE_SPEC,) * (3 * nb),
        out_specs=P(ROW_AXIS),
    )(X.blocks, *flat_args)
    return DistMultiVec(
        blocks=blocks, length=E.nrows, align="row", grid=E.grid
    )


def dist_spmm(
    sr: Semiring, E: EllParMat, X: DistMultiVec,
    backend: str | None = None,
) -> DistMultiVec:
    """The ROUTED entry: resolve the backend (``resolve_spmm_backend``),
    then run ``dist_spmm_ell``.  Callers that already know their
    backend (serve plans, which resolve once at engine build) call the
    jitted kernel directly."""
    backend = resolve_spmm_backend(sr, backend)
    return dist_spmm_ell(sr, E, X, backend=backend)


# -- fused k-hop propagation -------------------------------------------------


def row_invdeg(E: EllParMat) -> DistVec:
    """Row-aligned 1/max(deg, 1) float32 DistVec — the per-hop
    normalization vector of ``spmm_khop(..., normalize=True)``
    (value-identical to building a row-normalized twin matrix, without
    the second matrix)."""
    deg = E.reduce(
        PLUS_TIMES, "cols", map_fn=lambda v: jnp.ones_like(v, jnp.float32)
    )
    return dataclasses.replace(
        deg, blocks=1.0 / jnp.maximum(deg.blocks.astype(jnp.float32), 1.0)
    )


@partial(jax.jit, static_argnames=("sr", "k", "backend", "normalize"))
def _spmm_khop_impl(
    sr: Semiring, E: EllParMat, X: DistMultiVec, invdeg,
    k: int, backend: str, normalize: bool,
) -> DistMultiVec:
    """k chained hops, fully device-resident (ONE program: no host
    round-trip, no per-hop dispatch)."""
    if obs.ENABLED:
        obs.count(
            "trace.spmm_khop", hops=k, backend=backend,
            normalize=normalize,
        )
    Y = X
    for _ in range(max(int(k), 0)):
        Y = dist_spmm_ell(sr, E, Y, backend=backend)
        if normalize:
            # Y is row-aligned after the hop; invdeg is row-aligned —
            # Y ← D⁻¹(E·Y), the row-normalized smoothing step
            inv = invdeg.realign("row")
            Y = dataclasses.replace(
                Y, blocks=Y.blocks * inv.blocks[..., None]
            )
    return Y


def spmm_khop(
    sr: Semiring, E: EllParMat, X, k: int,
    normalize: bool = False, backend: str | None = None,
) -> DistMultiVec:
    """Fused k-hop feature propagation Y = (D⁻¹)ᵏAᵏ·X (normalize=True)
    or Aᵏ·X over ``sr``.

    ``X``: a DistMultiVec or a host ``[n, F]`` array (padded to the
    pow2 feature width and uploaded).  Hops chain device-resident; the
    backend resolves once (``resolve_spmm_backend``).  ``normalize`` is
    plus_times-only (a normalized min_plus has no meaning) and applies
    the row-degree reciprocal AFTER each hop.
    """
    if normalize and sr.name != "plus_times":
        raise ValueError(
            f"normalize=True needs plus_times, got {sr.name}"
        )
    if not isinstance(X, DistMultiVec):
        X = DistMultiVec.from_global(
            E.grid, pad_features(X), align="col"
        )
    backend = resolve_spmm_backend(sr, backend)
    invdeg = row_invdeg(E) if normalize else None
    return _spmm_khop_impl(
        sr, E, X, invdeg, int(k), backend, bool(normalize)
    )


# -- SUMMA SpMM over the 2D grid ---------------------------------------------


def _check_spmm_compat(A: SpParMat, X: DenseParMat):
    assert A.grid == X.grid, "A and X must share a grid"
    assert A.grid.is_square, "SUMMA SpMM requires a square grid"
    assert A.ncols == X.nrows, f"dim mismatch {A.ncols} != {X.nrows}"
    assert A.grid.local_cols(A.ncols) == A.grid.local_rows(X.nrows), (
        "A col-blocking must equal X row-blocking"
    )


def _stage_contract(
    sr: Semiring, t, xcur: Array, acc: Array, backend: str, mode: str,
    lr: int, lk: int,
):
    """acc ⊕= A_stage ⊗ X_stage for one carousel/gathered stage.

    ``mxu_gather``: densify the sparse stage tile with the COMBINING
    scatter (duplicate entries sum exactly — same dup-safety as the
    windowed tier's ``densify_combine``) and run the whole stage as one
    [lr, lk] × [lk, F] MXU product.  ``scatter``: per-tuple gather of
    the panel row + duplicate-safe combining scatter into the
    accumulator (every native add_kind)."""
    from .spgemm import _mxu_dot

    valid = t.valid_mask()
    if backend == "mxu_gather":
        da = jnp.zeros((lr, lk), acc.dtype).at[
            jnp.minimum(t.rows, lr - 1), jnp.minimum(t.cols, lk - 1)
        ].add(
            jnp.where(valid, t.vals, 0).astype(acc.dtype), mode="drop"
        )
        # the clamp above could alias a pad slot onto a real cell; the
        # where() already zeroed pad values so the alias adds 0
        return acc + _mxu_dot(da, xcur, mode, acc.dtype)
    F = xcur.shape[1]
    zero = sr.zero(acc.dtype)
    xpad = jnp.concatenate([xcur, jnp.full((1, F), zero, xcur.dtype)])
    px = xpad[jnp.minimum(t.cols, lk)]  # [cap, F]
    prods = sr.mul(t.vals[:, None].astype(acc.dtype), px.astype(acc.dtype))
    prods = jnp.where(valid[:, None], prods, zero)
    rows = jnp.where(valid, t.rows, lr)  # pad rows drop
    if sr.add_kind == "sum":
        return acc.at[rows].add(prods, mode="drop")
    if sr.add_kind == "min":
        return acc.at[rows].min(prods, mode="drop")
    if sr.add_kind == "max":
        return acc.at[rows].max(prods, mode="drop")
    raise NotImplementedError(
        f"summa_spmm scatter backend needs a native add_kind, "
        f"got {sr.add_kind!r} ({sr.name})"
    )


@partial(
    jax.jit,
    static_argnames=("sr", "backend", "mode", "ring", "pipeline"),
)
def summa_spmm(
    sr: Semiring,
    A: SpParMat,
    X: DenseParMat,
    *,
    backend: str = "mxu_gather",
    mode: str = "f32",
    ring: bool = False,
    pipeline: bool = True,
) -> DenseParMat:
    """C = A ⊗ X over the grid: SUMMA with a DENSE feature panel.

    X is tiled like SpGEMM's B (rows over grid rows, the F feature
    columns over grid columns), so stage s contracts A_{i,k(s)} against
    panel X_{k(s),j}.  ``ring=False`` gathers every stage operand up
    front (one fused all_gather per side — peak O(p·panel) dense
    memory); ``ring=True`` is the CAROUSEL: pre-skewed operands rotate
    one neighbor per stage (``_carousel_perms``, peak O(2·panel)), and
    ``pipeline=True`` issues stage s+1's ``ppermute`` BEFORE stage s's
    accumulate (two-slot buffers — the r9 latency-hiding schedule);
    ``pipeline=False`` pins the serial rotate→contract→rotate control
    with an optimization barrier (the measurement control).
    """
    from .spgemm import _carousel_stages_pair

    _check_spmm_compat(A, X)
    assert backend in SPMM_BACKENDS, backend
    if backend == "mxu_gather" and sr.name != "plus_times":
        raise ValueError(
            f"mxu_gather is the plus_times contraction; {sr.name} "
            "needs backend='scatter'"
        )
    grid = A.grid
    p = grid.pr
    lr = grid.local_rows(A.nrows)
    lk = grid.local_rows(X.nrows)
    out_dtype = jnp.result_type(A.vals.dtype, X.dtype)
    if obs.ENABLED:
        obs.count("trace.summa_spmm", ring=ring, backend=backend)
        if ring and pipeline and p > 1:
            obs.count("spmm.pipeline.stages_overlapped", p - 1)

    def body(ar, ac, av, an, xblk):
        a_mine = A.local_tile(ar, ac, av, an)
        x_mine = xblk[0, 0]  # [lk, fc]
        acc = jnp.full((lr, x_mine.shape[1]), sr.zero(out_dtype), out_dtype)
        if not ring:
            from .spgemm import _gather_stage_tiles

            a_st = _gather_stage_tiles(a_mine, COL_AXIS, p)
            x_all = lax.all_gather(x_mine, ROW_AXIS)  # [p, lk, fc]
            for s in range(p):
                acc = _stage_contract(
                    sr, a_st[s], x_all[s], acc, backend, mode, lr, lk
                )
        else:
            for s, a_cur, x_cur in _carousel_stages_pair(
                a_mine, x_mine, p, pipeline=pipeline, dep=lambda: acc
            ):
                acc = _stage_contract(
                    sr, a_cur, x_cur, acc, backend, mode, lr, lk
                )
        return acc[None, None]

    blocks = jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 5,
        out_specs=TILE_SPEC,
        check_vma=False,
    )(A.rows, A.cols, A.vals, A.nnz, X.blocks)
    return DenseParMat(
        blocks=blocks, nrows=A.nrows, ncols=X.ncols, grid=grid
    )


# -- backend routing --------------------------------------------------------


def resolve_spmm_backend(sr: Semiring, backend: str | None = None) -> str:
    """The SpMM backend: a ``backend`` argument vetted against
    ``admissible_spmm_backends(sr)``, else ``spmm_backend_heuristic(sr)``
    (plus_times → mxu_gather, else scatter).  Reads no file and no
    environment variable."""
    if backend is None:
        return spmm_backend_heuristic(sr)
    allowed = admissible_spmm_backends(sr)
    if backend not in allowed:
        raise ValueError(
            f"backend {backend!r} is not exact for {sr.name} "
            f"(admissible: {allowed})"
        )
    return backend
