"""Monoid segment-reductions — the TPU analog of the reference's SPA.

Every irregular accumulation in the reference (sparse accumulator / SPA in
``SpImpl.h:184-200`` + ``PreAllocatedSPA.h``, hash accumulation in
``mtSpGEMM.h:292-440``, heap merges in ``MultiwayMerge.h:185``) reduces to one
primitive: combine values that share a key with the semiring's ``add``.  On
TPU the native expression of that primitive is a segment reduction:

* monoids with an XLA scatter fast path (``sum`` / ``min`` / ``max``) lower to
  a single fused scatter op;
* arbitrary monoids use a sort-free segmented ``lax.associative_scan`` over
  values paired with their segment ids (ids must be pre-sorted, which our
  sorted-tuple invariant provides for free).

Out-of-range segment ids (>= num_segments) are dropped — this is how padded
(invalid) tuple slots stay inert without masks.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..semiring import Semiring


def segment_reduce(
    sr: Semiring,
    vals: jax.Array,
    ids: jax.Array,
    num_segments: int,
    *,
    ids_sorted: bool = False,
) -> jax.Array:
    """``out[s] = sr.add-fold of vals[ids == s]``; empty segments get ``sr.zero``.

    ids >= num_segments (padding) are dropped.
    """
    zero = sr.zero(vals.dtype)
    if sr.add_kind == "sum":
        # segment_sum's natural fill (0) is the additive identity of any
        # '+'-monoid — no empty-segment patch needed on the hottest path.
        # The sorted-indices hint is worth ~15-20% scatter throughput on
        # the round-3 machine (scatter probe; not re-measured).
        return jax.ops.segment_sum(
            vals, ids, num_segments=num_segments,
            indices_are_sorted=ids_sorted,
        )
    if sr.add_kind == "min":
        out = jax.ops.segment_min(
            vals, ids, num_segments=num_segments,
            indices_are_sorted=ids_sorted,
        )
    elif sr.add_kind == "max":
        out = jax.ops.segment_max(
            vals, ids, num_segments=num_segments,
            indices_are_sorted=ids_sorted,
        )
    else:
        return _generic_segment_reduce(
            sr, vals, ids, num_segments, ids_sorted=ids_sorted
        )
    # Natural identity of the scatter op may differ from the semiring zero
    # (e.g. select2nd_max has zero=-1 but segment_max fills INT_MIN); patch
    # empty segments.
    counts = jax.ops.segment_sum(
        jnp.ones_like(ids, dtype=jnp.int32), ids, num_segments=num_segments
    )
    return jnp.where(counts > 0, out, zero)


def _generic_segment_reduce(
    sr: Semiring,
    vals: jax.Array,
    ids: jax.Array,
    num_segments: int,
    *,
    ids_sorted: bool,
) -> jax.Array:
    zero = sr.zero(vals.dtype)
    if not ids_sorted:
        ids, vals = lax.sort((ids, vals), num_keys=1)

    def combine(a, b):
        va, ia = a
        vb, ib = b
        return jnp.where(ia == ib, sr.add(va, vb), vb), ib

    scanned_vals, _ = lax.associative_scan(combine, (vals, ids))
    # The last slot of each id-run holds the full fold; scatter it out.
    is_last = jnp.concatenate(
        [ids[1:] != ids[:-1], jnp.ones((1,), dtype=bool)]
    )
    scatter_ids = jnp.where(is_last, ids, num_segments)
    out = jnp.full((num_segments,), zero, dtype=vals.dtype)
    return out.at[scatter_ids].set(scanned_vals, mode="drop")


def expand_ranges(lens: jax.Array, capacity: int):
    """Flatten variable-length ranges into static-capacity slots.

    Given ``lens[i]`` items contributed by source ``i``, produce for each flat
    output slot ``f`` in ``[0, capacity)`` the pair ``(owner[f], offset[f])``
    such that slot ``f`` is item ``offset[f]`` of source ``owner[f]``, plus a
    validity mask (``f < sum(lens)``).

    This is the static-shape analog of the reference's per-column expansion
    loops in local SpGEMM (``mtSpGEMM.h:292-440``) and column walks in SpMSpV
    (``SpImpl.cpp:53-180``): instead of data-dependent loop bounds, we
    materialize a fixed ``capacity`` of slots and map each back to its source.

    The flop->owner map is computed by SCATTER + CUMULATIVE MAX, not
    searchsorted: scatter each source's index (and start) at its start
    position, then a streaming cummax fills the run. On the target chip a
    searchsorted here costs ~0.4 us per slot (measured 24.8 s of a 30.7 s
    scale-14 SpGEMM, round-3 scatter probe) while the
    two scatters touch only ``len(lens)`` slots and the cummaxes stream.
    """
    lens = lens.astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)]
    )
    total = starts[-1]
    n = lens.shape[0]
    pos = starts[:-1]  # scatter position of each source (>= capacity drops)
    # owner[f] = max{i : starts[i] <= f}; duplicates (zero-length sources)
    # resolve to the highest index, matching searchsorted(side='right') - 1.
    seed = jnp.full((capacity,), -1, jnp.int32).at[pos].max(
        jnp.arange(n, dtype=jnp.int32), mode="drop"
    )
    owner = jnp.clip(lax.cummax(seed), 0, n - 1)
    # base[f] = starts[owner[f]] by the same construction (starts monotone)
    base = jnp.zeros((capacity,), jnp.int32).at[pos].max(pos, mode="drop")
    base = lax.cummax(base)
    f = jnp.arange(capacity, dtype=jnp.int32)
    offset = f - base
    valid = f < total
    return owner, offset, valid, total
