"""Graph500 R-MAT edge generator — jittable, deterministic, TPU-resident.

The reference vendors the Graph500 v1.2/v2.1 generators (C, MRG random
stream) and drives them through ``DistEdgeList::GenGraph500Data``
(``DistEdgeList.cpp:223-330``, ``RefGen21.h:88-323``).  The TPU-native
re-design generates all edges on-device with ``jax.random`` (threefry is our
deterministic counter-based stream, replacing MRG) in one vectorized pass
over [nedges, scale] quadrant choices — no host loop, no MPI scatter; under
jit the edge list never leaves HBM.

Graph500 parameters: (A, B, C, D) = (0.57, 0.19, 0.19, 0.05), edgefactor 16,
per-level probability noise as in the spec's octave kernel, plus the random
vertex relabeling that ``DistEdgeList::RenameVertices`` applies.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnums=(1, 2, 3))
def rmat_edges(
    key: jax.Array,
    scale: int,
    nedges: int,
    noise: bool = True,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
):
    """Generate ``nedges`` R-MAT edges over 2**scale vertices.

    Returns (src, dst) int32 arrays. Self-loops and duplicates are NOT
    filtered (the reference keeps them in the edge list too and filters at
    matrix-build time, ``SpTuples`` Graph500 ctor).
    """
    d = 1.0 - a - b - c
    k_src, k_dst, k_noise, k_perm = jax.random.split(key, 4)
    u = jax.random.uniform(k_src, (nedges, scale))
    v = jax.random.uniform(k_dst, (nedges, scale))
    if noise:
        # Per-level multiplicative noise on A as in the Graph500 octave
        # kernel; renormalized via the conditional-probability formulation.
        mu = jax.random.uniform(k_noise, (nedges, scale), minval=0.95, maxval=1.05)
        a_eff = a * mu
    else:
        a_eff = jnp.full((nedges, scale), a)
    # P(src_bit=1) = 1 - (a + b); quadrant split conditioned on src_bit.
    ab = a_eff + b
    src_bit = u >= ab
    p_dst1 = jnp.where(src_bit, d / (c + d), b / ab)
    dst_bit = v < p_dst1
    weights = (1 << jnp.arange(scale, dtype=jnp.int32))[None, :]
    src = jnp.sum(src_bit.astype(jnp.int32) * weights, axis=1)
    dst = jnp.sum(dst_bit.astype(jnp.int32) * weights, axis=1)
    # Random vertex relabeling (≈ RenameVertices) to break the R-MAT
    # degree-locality correlation.
    n = 1 << scale
    perm = jax.random.permutation(k_perm, n)
    return perm[src].astype(jnp.int32), perm[dst].astype(jnp.int32)


def rmat_symmetric_coo_host(
    seed: int, scale: int, edgefactor: int = 16, noise: bool = True
):
    """Pure-numpy R-MAT (same kernel as ``rmat_edges``) → symmetrized COO.

    Numpy only, so a process that must not start a backend
    (``chip_smoke.py``'s parent and references) can build the graph, and a
    chip process only uploads. Deterministic in ``seed``.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    a, b, c = 0.57, 0.19, 0.19
    d = 1.0 - a - b - c
    n = 1 << scale
    nedges = edgefactor * n
    # Level-at-a-time generation: [nedges]-sized temporaries instead of
    # [nedges, scale] (a >10x peak-memory reduction — scale 21 would need
    # ~25 GB of float64 otherwise), identical output distribution.
    src = np.zeros(nedges, np.int64)
    dst = np.zeros(nedges, np.int64)
    for level in range(scale):
        u = rng.random(nedges)
        v = rng.random(nedges)
        a_eff = a * rng.uniform(0.95, 1.05, nedges) if noise else a
        ab = a_eff + b
        src_bit = u >= ab
        p_dst1 = np.where(src_bit, d / (c + d), b / ab)
        dst_bit = v < p_dst1
        w = np.int64(1) << level
        src += src_bit * w
        dst += dst_bit * w
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    rows = np.concatenate([src, dst])
    cols = np.concatenate([dst, src])
    return rows, cols


def rmat_symmetric_coo(key, scale: int, edgefactor: int = 16, noise: bool = True):
    """Edge list → symmetrized COO (both directions, no loops) on host.

    The app-level Symmetricize + RemoveLoops pipeline of the Graph500 drivers
    (``TopDownBFS.cpp:270-370``, ``SpParMat::RemoveLoops`` SpParMat.cpp:3257).
    Returns numpy (rows, cols) with duplicates retained (dedup at matrix
    construction).
    """
    import numpy as np

    n = 1 << scale
    src, dst = rmat_edges(key, scale, edgefactor * n, noise)
    src = np.asarray(src)
    dst = np.asarray(dst)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    rows = np.concatenate([src, dst])
    cols = np.concatenate([dst, src])
    return rows, cols
