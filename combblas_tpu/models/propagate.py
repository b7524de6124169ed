"""k-hop feature propagation — the graph-ML serving workload.

Embedding smoothing / recommendation-shaped traffic: every node carries
a dense feature row and queries want the k-hop NEIGHBORHOOD AGGREGATE
``(D⁻¹A)ᵏ·X`` (normalized) or ``Aᵏ·X`` — the SGC/LightGCN-style
propagation step, which is exactly the batched SpMM lane
(``parallel/spmm.py``) applied k times device-resident.

Two entries:

* :func:`propagate_features` — the whole-graph model API: host
  ``[n, F]`` features in, propagated ``[n, F]`` out (one fused
  ``spmm_khop`` launch; the backend is ``resolve_spmm_backend``'s).

* :func:`_propagate_batch_impl` — the SERVE plan body (kind
  ``"propagate"``): a W-lane batch of root queries answered WITHOUT
  touching the full feature table per query.  Lane w's result is row
  ``v_w`` of ``(D⁻¹A)ᵏX``, computed by propagating the batch's
  indicator block through the TRANSPOSE operator —

      e_vᵀ(D⁻¹A)ᵏX  ==  ((AᵀD⁻¹)ᵏ e_v)ᵀ X

  so the k hops are ``dist_spmm_ell`` calls over a [n, W] dense block
  (per-batch cost scales with W, not with n·F), and the feature table
  enters once at the end as ONE [W, n] × [n, F]-shaped MXU contraction
  (psum over grid rows).  ``PAD_ROOT`` lanes have all-zero indicators:
  structurally inert, zero features out — the serve batcher's pad
  contract holds with no special casing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from . import PAD_ROOT
from .bfs import _global_ids
from ..parallel.grid import ROW_AXIS
from ..semiring import PLUS_TIMES


def propagate_features(
    E, X, k: int, normalize: bool = False, sr=PLUS_TIMES,
    backend: str | None = None,
) -> np.ndarray:
    """Whole-graph k-hop propagation: host ``[n, F]`` features →
    propagated host ``[n, F]`` (pow2 pad lanes stripped).  ``E`` is an
    ``EllParMat`` in the usual gather orientation (entry (i, j) = edge
    j → i): each hop aggregates IN-neighbor features; ``normalize``
    divides by the in-degree per hop (plus_times only)."""
    from ..parallel.spmm import spmm_khop

    F = int(np.asarray(X).shape[1])
    Y = spmm_khop(sr, E, X, k, normalize=normalize, backend=backend)
    return np.asarray(Y.to_global())[:, :F]


def _propagate_batch_impl(
    ET, X, invdeg, sources, *, hops: int, normalize: bool,
    backend: str,
):
    """W root queries → ``[F, W]`` propagated feature columns (lane
    axis LAST, the serve scatter contract).

    ``ET``: the hop operator in TRANSPOSE orientation (the engine's
    ``ET`` property — E itself on symmetric graphs); ``X``: row-aligned
    ``DistMultiVec`` feature table (pow2-padded F); ``invdeg``:
    col-aligned 1/deg ``DistVec`` when ``normalize`` else None;
    ``sources``: int32 [W] with ``PAD_ROOT`` pad slots."""
    import dataclasses

    from ..parallel.spmm import dist_spmm_ell
    from ..parallel.vec import DistMultiVec

    grid = ET.grid
    n = ET.ncols
    pc_, lc = grid.pc, grid.local_cols(n)
    col_gids = _global_ids(grid, pc_, lc, n, "col")
    src = sources.astype(jnp.int32)[None, None, :]  # [1, 1, W]
    live = src != PAD_ROOT
    # PAD_ROOT lanes: live=False keeps the pad source from matching the
    # -1 padding slots of the gid table — an all-zero indicator column,
    # inert through every hop and the final contraction
    q0 = ((col_gids[:, :, None] == src) & live).astype(jnp.float32)
    Q = DistMultiVec(blocks=q0, length=n, align="col", grid=grid)
    for _ in range(max(int(hops), 0)):
        if normalize:
            # (AᵀD⁻¹)Q: scale by the reciprocal degree BEFORE the
            # transpose hop — the adjoint of spmm_khop's post-hop
            # row normalization
            Qc = Q.realign("col")
            Q = dataclasses.replace(
                Qc, blocks=Qc.blocks * invdeg.blocks[..., None]
            )
        Q = dist_spmm_ell(PLUS_TIMES, ET, Q, backend=backend)
    Qr = Q.realign("row")

    def body(xb, qb):
        # one [F, L] × [L, W] MXU contraction per grid row, reduced
        # over the row axis — the only place the feature table is read
        r = jnp.dot(
            xb[0].T, qb[0], preferred_element_type=jnp.float32
        )
        return lax.psum(r, ROW_AXIS)

    return jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(P(ROW_AXIS), P(ROW_AXIS)),
        out_specs=P(),
    )(X.blocks, Qr.blocks)
