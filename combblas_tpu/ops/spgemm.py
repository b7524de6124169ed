"""Local semiring SpGEMM — expansion / sort / compression (ESC).

The reference's local SpGEMM (``include/CombBLAS/mtSpGEMM.h:214-440``) runs a
two-pass symbolic+numeric hash/heap kernel with a per-column heap-vs-hash
choice (compression ratio < 2.0 → heap, :310-311) and OpenMP over columns.
Per-column dynamic hashing is hostile to TPU vectorization, so the TPU-native
kernel is the classic ESC formulation — every phase is a primitive XLA is
good at:

  1. EXPAND: one slot per scalar multiply (flop). For A entry (i,k,a) and
     B's row k, emit (i, j, a⊗b) for each (k,j,b) — flattened to a static
     ``flop_capacity`` via ``expand_ranges`` (no per-column loops).
  2. SORT: lexicographic (row, col) ``lax.sort`` — TPU's native sort.
  3. COMPRESS: segmented semiring fold + compaction (``SpTuples.compact``).

The symbolic pass of the reference (``estimateFLOP`` :1058,
``estimateNNZ_Hash`` :807) maps to ``flops`` below: exact flop counting is a
one-gather + segment-sum, and callers size ``flop_capacity`` from it outside
jit (capacities are trace-time constants — the XLA analog of the
reference's exact preallocation).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..semiring import Semiring
from .compressed import CSR
from .segment import expand_ranges
from .tuples import SpTuples

Array = jax.Array

#: Semiring add-monoid → XLA scatter combiner with a native lowering.
#: The dense-accumulator SpGEMM tier folds expansion slots straight into a
#: dense block with ``acc.at[idx].<combiner>`` — available exactly for the
#: monoids XLA can scatter-combine (the same add_kind fast-path contract
#: as ``ops/segment.py``); ``None`` means the tier must fall back to ESC.
_SCATTER_COMBINERS = {"sum": "add", "min": "min", "max": "max"}


def scatter_combine_for(sr: Semiring) -> str | None:
    """Name of the ``jnp.ndarray.at[...]`` combiner implementing
    ``sr.add`` (``"add"``/``"min"``/``"max"``), or None for generic
    monoids (which need the order-respecting segmented reduction)."""
    return _SCATTER_COMBINERS.get(sr.add_kind)


def flops(a: SpTuples, b_csr: CSR) -> Array:
    """Scalar-multiply count of a·b (≈ estimateFLOP, mtSpGEMM.h:1058).

    Accumulated in float32: true counts can exceed int32 at scale (the
    reference uses int64, which JAX disables by default), and a capacity
    estimate only needs ~7 significant digits — callers add multiplicative
    slack (see ``summa_capacities``).
    """
    assert a.ncols == b_csr.nrows
    lens_pad = jnp.concatenate([b_csr.row_lens(), jnp.zeros((1,), jnp.int32)])
    k = jnp.minimum(a.cols, b_csr.nrows)
    per_entry = jnp.where(a.valid_mask(), lens_pad[k], 0)
    return jnp.sum(per_entry.astype(jnp.float32))


#: Contiguous-lane width of the chunked expansion. The target chip's gather
#: unit is per-INDEX bound with payload lanes up to ~256 B nearly free
#: (round-2 notes; PERF.md §5 on today's chip), while per-element random
#: gathers run only ~22-27 M/s at every table size
#: (round-3 scatter probe) — so fetching B rows in W-wide contiguous
#: windows divides the expansion's gather count by ~W. Slot padding from
#: rounding each B-row walk up to W is 3-6% on R-MAT at W=32 (flops
#: concentrate in wide rows); ``flops_padded`` sizes it exactly.
CHUNK_W = 32


def flops_padded(a: SpTuples, b_csr: CSR, chunk_w: int = CHUNK_W) -> Array:
    """Slot count of the chunked expansion: per A-entry
    ``ceil(deg_B(col)/W) * W`` summed (>= ``flops``; the capacity
    ``expand`` actually needs).

    EXACT (unlike the float32-accumulated ``flops`` estimate): the CHUNK
    count sums in int32 (exact below 2^31 chunks ≈ 7e10 slots at W=32,
    far past HBM) and the float32 result is a multiple of W below
    2^24 * W slots, hence exactly representable — callers may pass
    ``int(flops_padded(...))`` with no slack.
    """
    assert a.ncols == b_csr.nrows
    lens_pad = jnp.concatenate([b_csr.row_lens(), jnp.zeros((1,), jnp.int32)])
    k = jnp.minimum(a.cols, b_csr.nrows)
    deg = jnp.where(a.valid_mask(), lens_pad[k], 0)
    nch = -(-deg // chunk_w)
    return jnp.sum(nch).astype(jnp.float32) * chunk_w


def expand(
    sr: Semiring,
    a: SpTuples,
    b_csr: CSR,
    flop_capacity: int,
    chunk_w: int = CHUNK_W,
) -> SpTuples:
    """EXPAND phase: uncombined product tuples (duplicates included).

    Output tile has shape (a.nrows, b.ncols) and capacity
    ``ceil(flop_capacity / chunk_w) * chunk_w``; work beyond it is silently
    truncated — callers must size via ``flops_padded`` (for exactness) or a
    proven bound.

    CHUNKED-ELL FORMULATION (round 3): one expansion slot per
    (A-entry, B-row W-chunk) instead of per flop. Each virtual entry
    issues ONE gather index whose payload is a contiguous W-window of B's
    indices/values (vmapped ``dynamic_slice`` → an XLA gather with
    ``slice_sizes=W`` — the same contiguous-lane pattern as the ELL SpMV,
    which the chip serves at ~130 M windows/s vs ~25 M/s for per-element
    gathers). The flop->owner map itself is the scatter+cummax
    ``expand_ranges`` over chunk counts (V ≈ flops/W entries instead of
    flops), so the whole phase does O(nnz + flops/W) random work plus
    streaming passes.
    """
    assert a.ncols == b_csr.nrows
    W = chunk_w
    v_capacity = -(-flop_capacity // W)
    # Pad one full window of sentinels: a row's last chunk may extend past
    # the valid data, and dynamic_slice would otherwise CLAMP the start
    # backward, silently gathering earlier rows' entries into live lanes.
    b_indices = jnp.concatenate(
        [b_csr.indices, jnp.full((W,), b_csr.ncols, jnp.int32)]
    )
    b_vals = jnp.concatenate(
        [b_csr.vals, jnp.zeros((W,), b_csr.vals.dtype)]
    )
    lens_pad = jnp.concatenate([b_csr.row_lens(), jnp.zeros((1,), jnp.int32)])
    starts_pad = jnp.concatenate([b_csr.indptr[:-1], jnp.zeros((1,), jnp.int32)])
    k = jnp.minimum(a.cols, b_csr.nrows)
    deg = jnp.where(a.valid_mask(), lens_pad[k], 0)
    nch = -(-deg // W)
    owner, chix, valid_v, _ = expand_ranges(nch, v_capacity)
    # per-virtual-entry (V-sized) gathers — V ≈ flops/W, all small tables
    a_rows_v = a.rows[owner]
    a_vals_v = a.vals[owner]
    k_v = jnp.minimum(a.cols[owner], b_csr.nrows)
    deg_v = lens_pad[k_v]
    b0 = jnp.where(valid_v, starts_pad[k_v] + chix * W, 0)
    # contiguous W-window gathers of B's indices and values
    # [V, W] computed-index gather; vmap(dynamic_slice) was measured 5-10x
    # SLOWER on the target chip despite its explicit contiguity (the
    # slice-gather lowering serializes; round-3 capture)
    win = b0[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    bcols = b_indices[win]
    bvals = b_vals[win]
    lane = jnp.arange(W, dtype=jnp.int32)
    lane_ok = valid_v[:, None] & (chix[:, None] * W + lane[None, :] < deg_v[:, None])
    rows = jnp.where(lane_ok, a_rows_v[:, None], a.nrows).reshape(-1)
    cols = jnp.where(lane_ok, bcols, b_csr.ncols).reshape(-1)
    vals = sr.mul(a_vals_v[:, None], bvals).reshape(-1)
    return SpTuples(
        rows=rows,
        cols=cols,
        vals=vals,
        nnz=jnp.sum(lane_ok).astype(jnp.int32),
        nrows=a.nrows,
        ncols=b_csr.ncols,
    )


def local_spgemm(
    sr: Semiring,
    a: SpTuples,
    b_csr: CSR,
    *,
    flop_capacity: int,
    out_capacity: int,
) -> SpTuples:
    """C = A ⊗ B on one tile: expand → sort → compress.

    ≈ ``LocalHybridSpGEMM`` (mtSpGEMM.h:214) with the hash/heap accumulator
    replaced by sort+segmented-fold.
    """
    return expand(sr, a, b_csr, flop_capacity).compact(
        sr, capacity=out_capacity
    )


def densify(t: SpTuples, pad_rows: int, pad_cols: int, zero) -> Array:
    """Tile tuples → dense [pad_rows, pad_cols] (padding cells = ``zero``).

    The scatter uses sorted/unique index hints (tiles are compacted and
    row-major sortable), which XLA can turn into a vectorized store.
    """
    t = t.sort_rowmajor()
    # Invalid slots get DISTINCT out-of-bounds indices (base + slot id) so
    # the unique_indices contract holds even for padding; mode='drop'
    # discards them all. Sortedness survives: valid entries occupy an
    # ascending prefix below base, invalid tail slots get base + position.
    oob = pad_rows * pad_cols + jnp.arange(t.capacity, dtype=jnp.int32)
    flat = jnp.where(t.valid_mask(), t.rows * pad_cols + t.cols, oob)
    dense = jnp.full((pad_rows * pad_cols,), zero, t.vals.dtype)
    dense = dense.at[flat].set(
        t.vals, mode="drop", indices_are_sorted=True, unique_indices=True
    )
    return dense.reshape(pad_rows, pad_cols)


#: The longest list ``sparsify_windowed`` sorts at once, in cells (a
#: power of two).  A window of more cells is sorted a group of
#: consecutive rows at a time (``sparsify_groups``); the ladder that
#: chose it is in ``sparsify_windowed``'s docstring.
SPARSIFY_GROUP_CELLS = 1 << 14


def sparsify_groups(R: int, C: int) -> int:
    """How many groups of consecutive rows ``sparsify_windowed`` sorts
    an [R, C] window in, read from the shape alone: 1 where the window
    has at most ``SPARSIFY_GROUP_CELLS`` cells (one flat sort), else R
    over the largest power of two that divides R and whose rows hold no
    more than that many cells (1 row where a single row is longer)."""
    if R * C <= SPARSIFY_GROUP_CELLS:
        return 1
    g = max(1, SPARSIFY_GROUP_CELLS // C)
    return R // math.gcd(1 << (g.bit_length() - 1), R)


def _lay_prefixes(key: Array, vals: Array) -> tuple[Array, Array]:
    """Sorted groups ``[G, L]``, each holding its kept cells as a prefix
    and the sentinel ``G * L`` after it, → the prefixes end to end in
    ``[G * L]``, sentinels (and zeros) after them.

    An ascending overlapped copy: group i writes ALL its slots at the
    sum of the counts before it, its tail of sentinels lands where
    group i + 1 starts, and group i + 1 overwrites it.  The buffers
    carry one group's slots of slack because ``dynamic_update_slice``
    clamps a start that would run past the end.  A trip slices its
    group from the FLAT sorted arrays: a row of the tiled ``[G, L]``
    is an operation more a trip, and a trip is what the loop costs
    (4-5 us on the v5e; ``sparsify_windowed`` has the ladder)."""
    G, L = key.shape
    cells = G * L
    cnt = jnp.sum(key < cells, axis=1, dtype=jnp.int32)
    off = jnp.cumsum(cnt) - cnt
    flat = key.reshape(-1), vals.reshape(-1)

    def lay(i, out):
        return tuple(
            lax.dynamic_update_slice(
                o, lax.dynamic_slice(x, (i * L,), (L,)), (off[i],))
            for o, x in zip(out, flat)
        )

    out_key, out_vals = lax.fori_loop(0, G, lay, (
        jnp.full((cells + L,), cells, key.dtype),
        jnp.zeros((cells + L,), vals.dtype),
    ))
    return out_key[:cells], out_vals[:cells]


def sparsify_windowed(
    dense: Array, zero, nrows: int, ncols: int, capacity: int
) -> tuple[SpTuples, Array]:
    """Dense [R, C] → compacted row-major SpTuples by sorting: every
    cell's key is its own row-major index where it holds a value and
    R*C where it does not, and the values ride along.  A window of at
    most ``SPARSIFY_GROUP_CELLS`` cells is ONE flat sort; a larger one
    is sorted a group of rows at a time and the groups' prefixes are
    laid end to end (``_lay_prefixes``).

    What an extraction costs on the v5e is what it moves one element at
    a time (my chip runs, PR 40, one [4096, 8192] window, 15% of its
    cells set, capacity = its cells): a gather of 33.5 M f32 by
    ascending indices 935 ms (28 ns each), a scatter of as many int32 to
    ascending slots 237 ms (7 ns each), whereas a sort of 33.5 M int32
    keys is 81 ms and a running sum over them 8 ms.  The whole
    extraction measured 116 ms a window this way, 485 ms as a running
    count and two scatters (indices, values) and 877 ms as one scatter
    and the gather of the values; the output-driven scheme this
    replaces (round 4: group-count tables and two "contiguous window"
    gathers, 16 + 8 lanes, a SLOT of capacity; its cost model came from
    a machine that is gone) was 81.1 s a product job at scale 14; with
    this one the same job is 1.1 s, 0.6 s of it here.

    A sort's cost follows the length of the axis it sorts, not the
    bytes it moves, so the grain pays (my chip runs, PR 41, the same
    window, best of three, ms: the ``[G, L]`` sort alone, the copy that
    lays the prefixes end to end, the whole extraction;
    ``scripts/sparsify_ladder.py``):

    ======================  =====  ====  ====  ==========
    cells a group (rows)    G      sort  copy  extraction
    ======================  =====  ====  ====  ==========
    2^25 (4,096): flat      1      74.2  -     76.3
    2^22 (512)              8      77.3  5.3   75.2
    2^20 (128)              32     59.5  5.5   57.8
    2^19 (64)               64     44.6  5.7   50.1
    2^18 (32)               128    59.6  6.9   65.3
    2^17 (16)               256    49.5  7.0   56.4
    2^16 (8)                512    41.9  7.0   48.3
    2^15 (4)                1,024  35.6  7.7   41.2
    2^14 (2): shipped       2,048  27.3  10.6  38.0
    2^13 (1)                4,096  21.9  16.7  38.5
    ======================  =====  ====  ====  ==========

    The copy is the bytes (5-7 ms for two arrays of 134 MB at
    unaligned offsets) up to some 500 trips and 4-5 us a trip after;
    the sorts between the ends are no smooth curve (2^18 is slower
    than 2^19).  With the grain at 2^14 the job above is 0.81 s,
    0.30 s of it here.

    Exact, sorted row-major, valid entries a prefix; ``total`` is the
    nonzero count before any truncation to ``capacity``.
    """
    R, C = dense.shape
    cells = R * C
    # fence: without it XLA rematerializes the PRODUCER of `dense` (e.g.
    # the whole MXU matmul) inside the passes below
    dense = lax.optimization_barrier(dense)
    mask = dense != zero
    if C != ncols:
        mask = mask & (jnp.arange(C, dtype=jnp.int32)[None, :] < ncols)
    if R != nrows:
        mask = mask & (jnp.arange(R, dtype=jnp.int32)[:, None] < nrows)
    mask = mask.reshape(-1)
    total = jnp.sum(mask, dtype=jnp.int32)
    key = jnp.where(mask, jnp.arange(cells, dtype=jnp.int32), cells)
    groups = sparsify_groups(R, C)
    # the kept cells' keys are distinct: no tie-break operand to carry;
    # one group stays a FLAT sort ([1, cells] along axis 1 is another
    # program on the chip: 426 ms where the flat one is 74)
    if groups == 1:
        key, vals = lax.sort(
            (key, dense.reshape(-1)), num_keys=1, is_stable=False)
    else:
        # a group's keys all lie under the next group's: the flat sort's
        # result is the sorted groups' prefixes laid end to end
        key, vals = _lay_prefixes(*lax.sort(
            (key.reshape(groups, -1), dense.reshape(groups, -1)),
            dimension=1, num_keys=1, is_stable=False))
    if capacity <= cells:
        key, vals = key[:capacity], vals[:capacity]
    else:
        key = jnp.pad(key, (0, capacity - cells), constant_values=cells)
        vals = jnp.pad(vals, (0, capacity - cells))
    valid = key < cells
    return (
        SpTuples(
            rows=jnp.where(valid, key // C, nrows).astype(jnp.int32),
            cols=jnp.where(valid, key % C, ncols).astype(jnp.int32),
            vals=jnp.where(valid, vals, 0),
            nnz=jnp.minimum(total, capacity).astype(jnp.int32),
            nrows=nrows, ncols=ncols,
        ),
        total,
    )


def sparsify(
    dense: Array, zero, nrows: int, ncols: int, capacity: int
) -> tuple[SpTuples, Array]:
    """Dense [R, C] block → (SpTuples with ``capacity`` slots, exact
    nonzero count).

    Row-structured extraction: per-row nonzero counts feed
    ``expand_ranges`` (whose binary search runs over the tiny [R+1]
    prefix array — cache-resident), and each slot finds its column with a
    manual binary search over its OWN row's prefix sums. A flat
    searchsorted over the full R*C cumsum measured 26 s for 33M queries
    on the target chip (0.78 us/query of HBM-random binary probes); the
    row-local formulation cuts the big-array probes ~2x and keeps the
    heavy first search in cache.
    """
    from .segment import expand_ranges

    R, C = dense.shape
    mask = dense != zero
    if C != ncols:
        mask = mask & (jnp.arange(C, dtype=jnp.int32)[None, :] < ncols)
    if R != nrows:
        mask = mask & (jnp.arange(R, dtype=jnp.int32)[:, None] < nrows)
    m32 = mask.astype(jnp.int32)
    rowcnt = jnp.sum(m32, axis=1)
    rowcum = jnp.cumsum(m32, axis=1).reshape(-1)  # flat [R*C]
    owner, offset, valid, total = expand_ranges(rowcnt, capacity)
    # smallest c with rowcum[owner, c] >= offset+1
    want = offset + 1
    lo = jnp.zeros((capacity,), jnp.int32)
    hi = jnp.full((capacity,), C - 1, jnp.int32)
    nsteps = max(int(np.ceil(np.log2(max(C, 2)))), 1)
    base = owner * C
    for _ in range(nsteps):
        mid = (lo + hi) >> 1
        v = rowcum[base + mid]
        lo = jnp.where(v < want, mid + 1, lo)
        hi = jnp.where(v < want, hi, mid)
    col = hi
    rows = jnp.where(valid, owner, nrows).astype(jnp.int32)
    cols = jnp.where(valid, col, ncols).astype(jnp.int32)
    vals = jnp.where(valid, dense.reshape(-1)[base + col], 0)
    return (
        SpTuples(
            rows=rows, cols=cols, vals=vals,
            nnz=jnp.minimum(total, capacity).astype(jnp.int32),
            nrows=nrows, ncols=ncols,
        ),
        total,
    )


# --- dense-accumulator block kernel (the windowed mid-scale tier) -----------


def accumulate_block_scatter(
    sr: Semiring,
    acc: Array,
    a: SpTuples,
    b_csr: CSR,
    *,
    row_lo: int,
    flop_capacity: int,
    chunk_w: int = 8,
) -> Array:
    """Fold one stage's expansion for output rows [row_lo, row_lo + Rb)
    into the dense accumulator ``acc`` [Rb, pad_cols] with a single
    semiring scatter — the sort-free ESC accumulate.

    The classic ESC pays a (row, col) sort over EVERY expansion slot to
    group duplicates; when the add monoid has a native scatter combiner
    (``scatter_combine_for``), grouping is instead one ``at[].{add,min,
    max}`` into a dense row block.  Expansion slots arrive row-major-ish
    (they follow A's entry order), so the scatter's write set walks the
    accumulator block-locally — on backends with cached scatter units
    (XLA:CPU) this runs ~7x the fully-random scatter rate, and the sort
    (the 87 s scale-16 ESC floor) disappears entirely.  On the target TPU
    (no scatter unit, round-4 notes) the caller uses the ``dot`` backend
    instead; this function is the general-backend twin.

    ``a`` must already be row-masked to the block (rows outside the block
    carry the ``a.nrows`` sentinel): invalid slots produce flat indices
    >= Rb * pad_cols and are dropped by the scatter.  ``chunk_w`` is the
    expansion window width — the default 8 keeps slot padding ~1.1x for
    R-MAT-like degree tails (the scatter pays per SLOT, so padding is
    priced at full scatter cost here, unlike the gather-bound ESC
    expansion where W=32 amortizes indices).
    """
    comb = scatter_combine_for(sr)
    assert comb is not None, (
        f"semiring {sr.name} (add_kind={sr.add_kind}) has no scatter "
        "combiner; use the ESC path"
    )
    rb, pad_cols = acc.shape
    t = expand(sr, a, b_csr, flop_capacity, chunk_w=chunk_w)
    # invalid slots: rows == a.nrows >= row_lo + rb ⇒ flat >= rb*pad_cols
    flat = (t.rows - row_lo) * pad_cols + t.cols
    flat = jnp.where(t.valid_mask(), flat, rb * pad_cols)
    upd = getattr(acc.reshape(-1).at[flat], comb)(
        t.vals, mode="drop"
    )
    return upd.reshape(rb, pad_cols)


def mask_rows(t: SpTuples, row_lo: int, row_hi: int) -> SpTuples:
    """Entries with row outside [row_lo, row_hi) become padding (sentinel
    indices) — the static row-block restriction of the windowed tier.
    ``nnz`` is recomputed; capacity is untouched (static shapes)."""
    import dataclasses

    keep = t.valid_mask() & (t.rows >= row_lo) & (t.rows < row_hi)
    return dataclasses.replace(
        t,
        rows=jnp.where(keep, t.rows, t.nrows),
        cols=jnp.where(keep, t.cols, t.ncols),
        nnz=jnp.sum(keep).astype(jnp.int32),
    )


def densify_combine(
    sr: Semiring, t: SpTuples, pad_rows: int, pad_cols: int
) -> Array:
    """Tile tuples → dense [pad_rows, pad_cols], duplicate slots COMBINED
    with the semiring's add monoid (``at[].{add,min,max}``).

    The duplicate-safe twin of ``densify``: that one claims
    ``unique_indices`` (undefined result on repeated (row, col) slots —
    the mxu tier's documented precondition), this one folds repeats with
    the same combiner the scatter backend uses, so every densifying
    consumer of it absorbs duplicate-entry COO inputs exactly.  No sort
    is needed (unsorted scatters combine associatively), which also makes
    it the cheaper choice for per-stage/per-window panel builds.  Only
    defined for semirings with a native scatter combiner
    (``scatter_combine_for``); cells with no entries hold ``sr.zero``.
    """
    comb = scatter_combine_for(sr)
    assert comb is not None, (
        f"semiring {sr.name} (add_kind={sr.add_kind}) has no scatter "
        "combiner; use densify on pre-compacted tiles instead"
    )
    zero = sr.zero(t.vals.dtype)
    ok = t.valid_mask() & (t.rows < pad_rows) & (t.cols < pad_cols)
    flat = jnp.where(ok, t.rows * pad_cols + t.cols, pad_rows * pad_cols)
    dense = jnp.full((pad_rows * pad_cols,), zero, t.vals.dtype)
    dense = getattr(dense.at[flat], comb)(t.vals, mode="drop")
    return dense.reshape(pad_rows, pad_cols)


def support_window_counts(
    bits: Array,
    block_rows: int,
    block_cols: int,
    nrows: int,
    ncols: int,
) -> Array:
    """Exact per-(row-block, col-window) output nnz from a packed support
    bitmask (``spgemm_support_bits`` / ``pack_support_bits`` layout):
    [nblocks, ncolwin] int32 — the oracle seeding of the 2D windowed
    plan (out caps become exact counts instead of clamped-flops bounds).

    ``block_cols`` must be word-aligned (multiple of 32) so every window
    covers whole uint32 words; bits past ``ncols`` are never set by the
    packers, so no tail masking is needed.
    """
    assert block_cols % 32 == 0, block_cols
    m, nw = bits.shape
    assert m == nrows, (m, nrows)
    nblocks = -(-nrows // block_rows)
    ncw = -(-ncols // block_cols)
    wpc = lax.population_count(bits).astype(jnp.int32)  # [m, nw]
    hid = (jnp.arange(nw, dtype=jnp.int32) * 32) // block_cols
    onehot = (hid[:, None] == jnp.arange(ncw, dtype=jnp.int32)[None, :])
    per_rh = jnp.dot(
        wpc.astype(jnp.float32), onehot.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)  # [m, ncw] (exact: counts < 2^24)
    g = jnp.arange(m, dtype=jnp.int32) // block_rows
    return jax.ops.segment_sum(per_rh, g, num_segments=nblocks)


# --- structure-aware merges (round 13: the sort-free combine tiers) ---------
#
# Every distributed SpGEMM schedule ends in the same step: combine the
# partial-product pieces that land on a device (SUMMA stage chunks, 3D
# fiber pieces) into one compacted tile.  The classic path is
# concat + full ``lax.sort`` (``SpTuples.compact``) — O(nnz·log nnz)
# comparisons over the WHOLE concatenation, re-deriving order the
# pieces already have.  The reference's distributed hash-SpGEMM (the
# 4.88 s scale-22 bar, SURVEY §2.2) never pays that sort; these two
# tiers are its TPU-native analogs:
#
#   ``merge_sorted_runs``  pieces that are already (row, col)-sorted
#                          (windowed-tier extractions, pre-sorted fiber
#                          pieces) merge by rank-space union — each
#                          element finds its output slot with
#                          lexicographic binary searches against the
#                          OTHER runs (O(nnz·log L) search levels), no
#                          sort anywhere.  Bit-exact with concat+sort
#                          for every semiring: equal keys stay in run
#                          order, so the segmented fold sees the same
#                          operand order.
#   ``hash_merge``         high-collision reduces combine through a
#                          bounded open-addressing table (scatter-probe
#                          claim, semiring combine on hit) — O(nnz)
#                          expected work independent of run count, with
#                          a COUNTED overflow so callers fall back to
#                          the sorted merge (never wrong, only slower).


def _lex_searchsorted(rs: Array, cs: Array, rq: Array, cq: Array,
                      side: str = "left") -> Array:
    """Vectorized ``searchsorted`` over LEXICOGRAPHIC (row, col) keys:
    for each query (rq, cq), the count of entries in the sorted run
    (rs, cs) strictly less than it (``side="left"``) or
    less-or-equal (``side="right"``).

    A single fused int key overflows int32 for large tiles
    (row·ncols + col exceeds 2^31 well inside the windowed envelope),
    so the comparison stays two-key; the binary search runs
    ceil(log2(n+1)) vectorized steps of one gather each — the same
    in-register search pattern as ``sparsify``."""
    assert side in ("left", "right"), side
    n = rs.shape[0]
    lo = jnp.zeros(rq.shape, jnp.int32)
    hi = jnp.full(rq.shape, n, jnp.int32)
    nsteps = max(int(np.ceil(np.log2(n + 1))), 1)
    for _ in range(nsteps):
        mid = (lo + hi) >> 1
        rm = rs[jnp.minimum(mid, n - 1)]
        cm = cs[jnp.minimum(mid, n - 1)]
        if side == "left":
            before = (rm < rq) | ((rm == rq) & (cm < cq))
        else:
            before = (rm < rq) | ((rm == rq) & (cm <= cq))
        adv = (lo < hi) & before
        ret = (lo < hi) & ~before
        lo = jnp.where(adv, mid + 1, lo)
        hi = jnp.where(ret, mid, hi)
    return lo


def _merge_two_sorted(x: SpTuples, y: SpTuples) -> SpTuples:
    """Merge two (row, col)-sorted tiles (padding sentinels at the
    tail) into one sorted tile of capacity ``x.capacity + y.capacity``.

    Rank-space union: x[i]'s output slot is ``i + |{y < x[i]}|`` and
    y[j]'s is ``j + |{x <= y[j]}|`` — a permutation by construction
    (ties resolve x-before-y, preserving concat order, so a downstream
    segmented fold is BIT-EXACT with the concat+sort path even for
    order-sensitive float accumulation).  Sentinel slots (row == nrows)
    compare greater than every valid key and equal to each other, so
    they land — x's first, then y's — on the output tail: padding
    stays a suffix and ``valid_mask`` semantics survive."""
    assert (x.nrows, x.ncols) == (y.nrows, y.ncols), (x, y)
    mx, my = x.capacity, y.capacity
    px = jnp.arange(mx, dtype=jnp.int32) + _lex_searchsorted(
        y.rows, y.cols, x.rows, x.cols, side="left"
    )
    py = jnp.arange(my, dtype=jnp.int32) + _lex_searchsorted(
        x.rows, x.cols, y.rows, y.cols, side="right"
    )

    def weave(ax, ay):
        out = jnp.zeros((mx + my,), ax.dtype)
        out = out.at[px].set(ax, unique_indices=True)
        return out.at[py].set(ay, unique_indices=True)

    return SpTuples(
        rows=weave(x.rows, y.rows),
        cols=weave(x.cols, y.cols),
        vals=weave(x.vals, y.vals),
        nnz=x.nnz + y.nnz,
        nrows=x.nrows, ncols=x.ncols,
    )


def merge_sorted_runs(runs: list[SpTuples]) -> SpTuples:
    """k-way merge of (row, col)-sorted same-shape tiles into ONE
    sorted tile (duplicates preserved, adjacent) — the sort-free
    replacement for ``SpTuples.concat(runs).sort_rowmajor()``.

    Pairwise tree merge: ceil(log2(L)) levels of ``_merge_two_sorted``
    rank-space unions, O(total · log L) binary-search levels instead of
    the full sort's O(total · log total) comparison passes — and each
    level is gathers + two scatters, which the CPU/TPU backends serve
    far faster than ``lax.sort``'s data-movement passes.  Adjacent
    pairing keeps ties in ascending run order at every level, so the
    output's duplicate groups appear in EXACT concat order (the
    bit-exactness contract callers' ``compact(assume_sorted=True)``
    relies on).  Callers must guarantee each run is individually
    sorted; ``mesh3d._fiber_exchange(sort_pieces=True)`` is the
    pre-sort for producers that aren't."""
    assert runs, "merge_sorted_runs needs at least one run"
    while len(runs) > 1:
        nxt = [
            _merge_two_sorted(runs[i], runs[i + 1])
            for i in range(0, len(runs) - 1, 2)
        ]
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    return runs[0]


def hash_table_capacity(out_capacity: int) -> int:
    """Static open-addressing table size for ``hash_merge``: the next
    pow2 at or above 4× the distinct-key bound keeps the load factor
    ≤ 0.25.  With double hashing the chance an element exhausts k
    probes is ≈ α^k, so α=0.25 with the default 16 rounds puts the
    expected overflow (→ sorted-merge rerun) below 1e-9 per element —
    the fallback stays a safety net, not a steady-state tax.  (2×/8
    rounds measured ~3e-4 per element: one rerun per few thousand
    entries, far too hot for the multi-million-entry reduces this
    tier targets.)"""
    return 1 << max(int(4 * max(out_capacity, 8)) - 1, 1).bit_length()


def hash_merge(
    sr: Semiring,
    t: SpTuples,
    *,
    out_capacity: int,
    table_capacity: int,
    n_probes: int = 16,
) -> tuple[SpTuples, Array, Array]:
    """Combine duplicate (row, col) keys of ``t`` through a bounded
    open-addressing table — the hash-accumulator merge tier
    (≈ the reference's distributed hash-SpGEMM combine, SURVEY §2.2,
    with the per-column dynamic table replaced by ONE fixed
    ``table_capacity`` buffer and data-parallel scatter probing).

    Per probe round (static unroll, double hashing over the pow2
    table): unplaced elements gather their slot's key; empty slots are
    CLAIMED by a scatter-min winner which installs its key; every
    element whose slot now holds ITS key folds its value in with the
    add monoid's native scatter combiner and retires.  Elements still
    unplaced after ``n_probes`` rounds are COUNTED, not dropped —
    callers watch the overflow and rerun through the sorted-merge
    tier (never wrong, only slower).

    Returns ``(out, overflow, distinct)``: ``out`` is the compacted
    (UNSORTED — table-order) tile truncated to ``out_capacity``;
    ``distinct`` is the exact distinct-nonzero-key count so callers
    detect out_capacity truncation the usual way.  Only defined for
    semirings with a native scatter combiner."""
    comb = scatter_combine_for(sr)
    assert comb is not None, (
        f"semiring {sr.name} (add_kind={sr.add_kind}) has no scatter "
        "combiner; use merge_sorted_runs / the sort path"
    )
    T = int(table_capacity)
    assert T >= 2 and T & (T - 1) == 0, f"table capacity {T} not pow2"
    cap = t.capacity
    valid = t.valid_mask()
    zero = sr.zero(t.vals.dtype)

    def _mix(x):
        # finalizer-style avalanche (splitmix32 constants): adjacent
        # (row, col) keys — the common case for sorted pieces — must
        # not probe adjacent slots in lockstep
        x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
        x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
        return x ^ (x >> 16)

    k = (
        t.rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        + t.cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
    )
    h0 = (_mix(k) & jnp.uint32(T - 1)).astype(jnp.int32)
    # odd step cycles the whole pow2 table (double hashing)
    step = (
        (_mix(k ^ jnp.uint32(0xC2B2AE35)) | jnp.uint32(1))
        & jnp.uint32(T - 1)
    ).astype(jnp.int32) | 1
    t_rows = jnp.full((T,), t.nrows, jnp.int32)
    t_cols = jnp.full((T,), t.ncols, jnp.int32)
    t_vals = jnp.full((T,), zero, t.vals.dtype)
    slot_ids = jnp.arange(cap, dtype=jnp.int32)
    placed = ~valid
    slot = h0
    for round_ in range(n_probes):
        if round_:
            slot = (slot + step) & (T - 1)
        active = ~placed
        empty = t_rows[slot] == t.nrows
        # claim: lowest proposing element index wins each empty slot
        prop = jnp.where(active & empty, slot, T)
        winner = jnp.full((T,), cap, jnp.int32).at[prop].min(
            slot_ids, mode="drop"
        )
        inst = active & empty & (winner[slot] == slot_ids)
        # distinct OOB sentinels for non-installers (densify's
        # unique_indices convention)
        inst_slot = jnp.where(inst, slot, T + slot_ids)
        t_rows = t_rows.at[inst_slot].set(
            t.rows, mode="drop", unique_indices=True
        )
        t_cols = t_cols.at[inst_slot].set(
            t.cols, mode="drop", unique_indices=True
        )
        # combine into any slot now holding MY key (the installer and
        # every duplicate retire together)
        match = active & (t_rows[slot] == t.rows) & (t_cols[slot] == t.cols)
        t_vals = getattr(
            t_vals.at[jnp.where(match, slot, T)], comb
        )(t.vals, mode="drop")
        placed = placed | match
    overflow = jnp.sum(~placed).astype(jnp.int32)
    table = SpTuples(
        rows=t_rows, cols=t_cols, vals=t_vals,
        nnz=jnp.sum(t_rows < t.nrows).astype(jnp.int32),
        nrows=t.nrows, ncols=t.ncols,
    )
    # compact + prune additive identities (compact's prune_zeros
    # semantics), then truncate to the caller's static output shape
    out = table._select((t_rows < t.nrows) & (t_vals != zero))
    distinct = out.nnz
    return out.with_capacity(out_capacity), overflow, distinct


# --- bit-packed output-support oracle ---------------------------------------


def coo_sort_dedup(rows: Array, cols: Array) -> tuple[Array, Array, Array]:
    """Two-key sort (rows major, cols minor) + adjacent-repeat mask for
    a COO edge list.  Every bit-packed kernel must group and mask
    duplicated input entries on device (a duplicate would double-ADD a
    bit, carrying into the NEXT bit — ADVICE r5).  Returns the reordered
    (rows, cols) and the per-slot ``dup`` mask (True on every repeat
    after the first of a group).  Shared by the edge-harvest TC kernels
    (models/tc.py), ``pack_support_bits`` and
    ``parallel/spgemm.py:coo_has_duplicates``.

    The sort carries the list it orders: ONE ``lax.sort`` whose two
    operands are both keys, so no permutation exists and nothing is
    gathered through one (an element gather is 28 ns a slot on a v5e,
    one more operand of a sort next to nothing).  It is not stable and
    need not be: slots it may swap are equal in both lists, and a stable
    sort is compiled with an iota as one more operand.
    ``chiprun -- python scripts/tc_dedup_ladder.py`` at
    ``g500-s18tc.tc-batch``'s shape (n = 2^18, 7,611,536 slots; my chip
    run, PR 51, one v5e, best of three, the three within 0.3 ms), the
    ordered list with its mask: two stable ``argsort``s, each followed
    by two gathers through its permutation, 315.7 ms; this sort **12.9**
    (1.7 ns a slot), 19.0 if stable; two one-key passes, columns then
    rows, each carrying the other list, 38.6 both stable and 31.2 the
    first not.  Every rung's ``(rows, cols, dup)`` has the first's
    digest.  Re-run before changing the form."""
    rows, cols = lax.sort((rows, cols), num_keys=2, is_stable=False)
    dup = jnp.concatenate([
        jnp.zeros((1,), bool),
        (rows[1:] == rows[:-1]) & (cols[1:] == cols[:-1]),
    ])
    return rows, cols, dup


#: Lanes of an (8, 128) tile (``pallas_kernels.LANES``, not imported
#: here: Pallas is imported by the call that runs a kernel, as
#: parallel/spgemm.py does), and the words of ONE whole tile: a packed
#: row of a multiple of ``TILE_WORDS`` words (n a multiple of 32,768)
#: can be held as whole tiles, ``[n, nw / 128, 128]`` with nothing
#: padded.  A row of fewer (128 words at n = 4,096) would be one
#: sublane of a tile: the chip pads the table to 8 rows of tiles and
#: COPIES it into that layout, which a table of most of the chip's
#: memory cannot afford (the described-v5e compile at n = 36,864 shows
#: the copy; PERF.md section 6, PR 47).
LANES = 128
TILE_WORDS = 8 * LANES


def pack_support_bits(
    rows: Array,
    cols: Array,
    nrows: int,
    ncols: int,
    *,
    assume_unique: bool = False,
    row_tiles: bool = False,
) -> Array:
    """COO support → packed [nrows, ceil(ncols/32)] uint32 bitmask.

    Bit (i, j) is set iff some entry (i, j) exists with i < nrows and
    j < ncols — sentinel/padded slots (row >= nrows) drop out via the
    scatter's ``mode='drop'``.  Packing is a scatter-ADD of
    ``2^(j mod 32)`` at (i, j div 32); duplicates would carry into the
    next bit, so the input is ``coo_sort_dedup``-masked first unless the
    caller guarantees uniqueness (e.g. compacted SpTuples).

    This is the storage format of the output-support oracle: 32x less
    memory and gather traffic than a bool matrix, and intersection
    queries are ``popcount(a & b)`` (see ``popcount_pair_counts``).

    ``row_tiles`` (the word axis must divide by ``TILE_WORDS``) returns
    the same words as ``[nrows, nw / 128, 128]``: a row is then whole
    (8, 128) tiles, 32 KB in one piece at nw = 8192, which is what the
    fused harvest's row copies need.  Its writer writes those bytes
    itself: the table cannot be COPIED into another layout where it is
    most of the chip's memory (8.59 GB at n = 2^18).  Where a Pallas
    TPU kernel runs (``harvest_path(nw) == "fused"``) that writer is
    ``pallas_kernels.pack_rows``: every row is assembled in on-chip
    memory from the sorted list and stored once, empty rows as zeros,
    with no zero fill and no scatter into HBM (a scatter-add there is
    one dependent read-modify-write of the table a slot: 88 ns against
    5; ``PACK_GROUP``).  It walks the list in order, so with
    ``assume_unique`` the caller guarantees more there: ``rows`` ascend
    over the slots that count and ``cols`` within a row (what
    ``coo_sort_dedup`` returns); slots at ``rows >= nrows``, the
    dropped ones, may lie anywhere among them.  Everywhere else
    (``row_tiles=False``, a CPU) the scatter-add takes any order.
    """
    nw = -(-ncols // 32)
    if not assume_unique:
        rows, cols, dup = coo_sort_dedup(rows, cols)
        rows = jnp.where(dup, nrows, rows)
    oob = (rows >= nrows) | (cols >= ncols)
    r = jnp.where(oob, nrows, rows)
    word = cols >> 5
    bit = jnp.uint32(1) << (cols.astype(jnp.uint32) & 31)
    if row_tiles:
        assert nw % TILE_WORDS == 0, (ncols, nw)
        tiles = nw // LANES
        if harvest_path(nw) == "fused":
            # every row assembled in on-chip memory and written to HBM
            # once: no zero fill, no scatter (PERF.md section 6, PR 49)
            from .pallas_kernels import pack_rows

            group = math.gcd(nrows, PACK_GROUP)
            return pack_rows(
                *pack_rows_operands(
                    r, cols, nrows, nw, group=group, piece=PACK_PIECE),
                nrows, nw, group=group, piece=PACK_PIECE,
                interpret=_kernel_mode() == "interpret",
            ).reshape(nrows, tiles, LANES)
        # scattered as [nrows * tiles, 128] under two indices, which is
        # [nrows, tiles, 128] byte for byte under the chip's (8, 128)
        # tiling (the reshape is free); three indices cost the scatter
        # 42 ms more at n = 2^18 (HARVEST_GROUP)
        bits = jnp.zeros((nrows * tiles, LANES), jnp.uint32)
        tile = word >> (LANES.bit_length() - 1)
        return bits.at[r * tiles + tile, word & (LANES - 1)].add(
            bit, mode="drop").reshape(nrows, tiles, LANES)
    bits = jnp.zeros((nrows, nw), jnp.uint32)
    return bits.at[r, word].add(bit, mode="drop")


#: Table rows a step of the on-chip pack assembles and writes (its
#: output block: ``PACK_GROUP`` x 32 KB at nw = 8192, twice, pipelined:
#: 2 MB of on-chip memory) and the slots of the sorted list one copy
#: brings to scalar memory (two lists, two buffers each: 32 KB).
#: ``chiprun -- python scripts/tc_pack_ladder.py`` at the cell's shapes
#: (n = 2^18, 7,611,536 slots, the table 8.59 GB; my chip run, PR 49, one
#: v5e, the committed files, best of three, the three within 0.2 ms):
#: the zero fill and the scatter-add into HBM 673.3 ms, the same with
#: ``indices_are_sorted`` and ``unique_indices`` promised 673.5 (88.5 ns
#: a slot either way); a loop over slabs of 2,048 rows, each zeroed,
#: scatter-added and laid into the table with no kernel, 123.0 (512
#: rows: 277.8); the kernel with its operands 51.8 / 45.1 / 41.9 / 40.2
#: at 8 / 16 / 32 / 64 rows a group (the operands alone, a fill from the
#: left and the binary searches, are 9.5 / 7.0 / 5.8 / 5.1 of that),
#: 42.2 / 41.9 / 41.4 at 1,024 / 2,048 / 8,192 slots a piece, and 99.1 /
#: 51.5 / 45.4 / 41.9 / 41.2 at 1 / 4 / 8 / 16 / 32 slots an unrolled
#: chunk (``pallas_kernels.pack_rows``'s ``unroll``): 4.7 ns, 4-5
#: cycles, a slot, the table's 8.59 GB written under it (10.5 ms at the
#: roofline).  A first form of the kernel that took rows and columns and
#: tested every slot against its group read 75.2 at 8 slots a chunk and
#: 68.4 at 16 (``pack_rows_operands`` says what it is handed instead).
#: Every rung's table has the first's digest.  Re-run before moving
#: either.
PACK_GROUP = 32
PACK_PIECE = 2048


def pack_rows_operands(
    r: Array, cols: Array, nrows: int, nw: int, *, group: int, piece: int
) -> tuple[Array, Array, Array]:
    """What ``pallas_kernels.pack_rows`` takes, from the list
    ``pack_support_bits`` scatters: ``(at, pos, offsets)``.  ``r``
    ascends over the slots under ``nrows`` and ``cols`` within a row
    (the dropped slots, at ``nrows``, lie anywhere among them).

    The kernel is handed what would cost it a scalar operation a slot
    to find: the table's sublane a slot falls on (row x tiles + the
    column's tile of 4,096; a dropped slot takes its left neighbour's,
    so the list still ascends and the slot ORs nothing into the run it
    lies in), the column's place inside it, and where each group of
    ``group`` rows starts in the list: ``nrows / group + 1`` binary
    searches (an index a GROUP, not a row: a gather costs this chip
    28 ns an element).  The list is padded to whole ``piece``s with
    dropped slots."""
    tiles = nw // LANES
    pad = -r.shape[0] % piece if r.shape[0] else piece
    r = jnp.pad(r, (0, pad), constant_values=nrows)
    cols = jnp.pad(cols, (0, pad))
    kept = r < nrows
    sublane = 32 * LANES  # columns a sublane holds: 128 words of 32
    at = lax.cummax(jnp.where(kept, r * tiles + cols // sublane, 0))
    pos = jnp.where(kept, cols % sublane, sublane)
    offsets = jnp.searchsorted(
        at, jnp.arange(nrows // group + 1, dtype=jnp.int32) * (group * tiles))
    return at, pos, offsets.astype(jnp.int32)


def front_pack_pairs(
    keep: Array, ii: Array, jj: Array, *, chunk: int = 8192
) -> tuple[Array, Array, Array, Array]:
    """The pair list ``popcount_pair_counts`` walks, kept pairs FIRST.

    One stable sort on ``~keep`` carrying both index lists: the kept
    pairs come out as a prefix in the order they had (row-major after
    ``coo_sort_dedup``), the rest behind them clamped to pair (0, 0)
    under weight 0, and the whole is padded to a multiple of ``chunk``
    the same way.  A sort and not the cumsum + scatter of
    ``SpTuples._select``: the chip scatters some 11.5 M elements a
    second (PERF.md section 6, PR 37), a sort carries its operands
    along.

    Returns ``(ii, jj, weights, count)``; ``count`` (traced int32) is
    the length of the kept prefix, the ``count`` to hand the scan so it
    runs the steps that hold a kept pair and no more.
    """
    drop, ii, jj = lax.sort(
        (
            (~keep).astype(jnp.int32),
            jnp.where(keep, ii, 0),
            jnp.where(keep, jj, 0),
        ),
        num_keys=1,
        is_stable=True,
    )
    pad = (0, -ii.shape[0] % chunk)
    weights = jnp.pad(1 - drop, pad)
    return jnp.pad(ii, pad), jnp.pad(jj, pad), weights, jnp.sum(weights)


#: Pairs whose rows the fused harvest fetches at once (a group: its 2 x
#: ``HARVEST_GROUP`` row copies fly while the group before is counted;
#: 2 MB of on-chip buffers at 32 KB a row).  ``chiprun -- python
#: scripts/tc_harvest_ladder.py`` at the cell's shapes (n = 2^18,
#: 3,809,280 pairs, 249.6 GB of rows; my chip runs, PR 47, one v5e, best
#: of three, three runs within 0.1 ms of each other): the fused kernel
#: 360.64 / 350.58 / 350.66 / 351.11 ms a harvest at 8 / 16 / 32 / 64
#: pairs a group (712 GB/s, 87% of 819: from 16 up the copies' bandwidth
#: bounds it, not their issue rate; 16 is the least code to trace, lower
#: and compile at every boot: the kernel's copies are unrolled); the
#: ``jnp`` loop 1,217.5 ms at a step of 8,192 pairs (the loop before
#: PR 47) and 1,025.9 / 855.9 / 702.7 / 779.4 at 2,048 / 1,024 / 512 /
#: 256, where the compiler keeps both gathered blocks in its fast
#: memory.  The pack (zero fill + scatter-add) 669.9 ms into ``[n, nw]``,
#: 685.9 into ``[n * nw / 128, 128]`` (the whole-tile table, two
#: indices), 728.2 into ``[n, nw / 128, 128]`` under three.  Re-run
#: before moving the constant.
HARVEST_GROUP = 16


def _kernel_mode() -> str | None:
    """How this process runs a Pallas TPU kernel: ``"compiled"`` on a
    TPU, not at all (None) elsewhere.  Read from the backend, set by
    nobody: the tests alone put ``"interpret"`` (a CPU) or
    ``"compiled"`` (a described chip) here."""
    return "compiled" if jax.default_backend() == "tpu" else None


def harvest_path(nw: int) -> str:
    """Which loop ``popcount_pair_counts`` runs over tables of ``nw``
    words a row: ``"fused"`` (``pallas_kernels.pair_popcount_partials``:
    a pair's rows are fetched and counted in one kernel) where the
    backend is a TPU and a row is whole tiles (``nw % TILE_WORDS ==
    0``, n a multiple of 32,768), ``"jnp"`` (two row gathers, then the
    count) otherwise.  The caller packs its tables to match
    (``pack_support_bits(row_tiles=...)``)."""
    return "fused" if nw % TILE_WORDS == 0 and _kernel_mode() else "jnp"


def popcount_pair_counts(
    bits_i: Array,
    bits_j: Array,
    ii: Array,
    jj: Array,
    weights: Array,
    *,
    chunk: int = 8192,
    count: Array | None = None,
) -> Array:
    """Σ_pairs weights · popcount(bits_i[ii] ∩ bits_j[jj]) as an int32
    (hi, lo) 15-bit split (totals can exceed 2^31; int64 is unavailable
    without x64 mode — same rationale as models/tc.py).

    The masked-SpGEMM numeric pass for 0/1-valued plus_times products:
    each (i, j) pair's count is the exact C[i,j] = Σ_k A[i,k]·B[k,j]
    restricted to the pair list (the output-support mask).  A loop walks
    ``chunk``-sized pair blocks, each a slice of the list at the loop's
    counter — the bit-packed edge-harvest inner loop (models/tc.py)
    generalized to two distinct bit tables, which is what the
    DISTRIBUTED tier needs (row-block and col-block masks live on
    different devices).

    What a step does with its block is read from the tables
    (``harvest_path``).  Tables of whole-tile rows
    (``pack_support_bits(row_tiles=True)``: ``[n, nw / 128, 128]``)
    where a Pallas TPU kernel runs take the FUSED step: one kernel
    fetches a pair's two rows into on-chip memory, ``HARVEST_GROUP``
    pairs at a time, and counts them there, so a row crosses HBM once
    and a step writes 512 B a pair.  Any other tables take the ``jnp``
    step: two row gathers of ``[chunk, nw]`` words, written to HBM and
    read back by a streaming popcount (three crossings; PERF.md section
    6, PR 47).  Same pairs, same count.

    ``ii``/``jj``/``weights`` must be padded to a multiple of ``chunk``
    with weight-0 slots (indices clamped in-range by the caller).
    ``count`` (a traced int32; default: every pair) is how many LEADING
    pairs to walk: the loop runs ``ceil(count / chunk)`` steps, so a
    list with its weight-1 pairs in front (``front_pack_pairs``) costs
    the steps that hold one.
    """
    npairs = ii.shape[0]
    assert npairs % chunk == 0, (npairs, chunk)
    if count is None:
        steps = npairs // chunk
    else:
        steps = -(-jnp.minimum(count, npairs) // chunk)
    fused = bits_i.ndim == bits_j.ndim == 3 and _kernel_mode() is not None

    def body(k, carry):
        hi, lo = carry

        def cut(a):  # step k's chunk of the pair list: a slice, no gather
            return lax.dynamic_slice(a, (k * chunk,), (chunk,))

        if fused:
            from .pallas_kernels import pair_popcount_partials

            # a row copy has no bounds check: clamp as a gather would
            part = pair_popcount_partials(
                bits_i, bits_j,
                jnp.clip(cut(ii), 0, bits_i.shape[0] - 1),
                jnp.clip(cut(jj), 0, bits_j.shape[0] - 1),
                group=min(HARVEST_GROUP, chunk),
                interpret=_kernel_mode() == "interpret",
            )
            cnt = jnp.sum(part, axis=1) * cut(weights)
        else:
            # scopes (metadata only; models/tc.py:TC_SCOPES names them)
            with jax.named_scope("gather"):
                gi = bits_i[cut(ii)]  # [chunk, nw] u32
                gj = bits_j[cut(jj)]
            with jax.named_scope("popcount"):
                pc = lax.population_count(gi & gj)
                cnt = jnp.sum(
                    pc.astype(jnp.int32), axis=tuple(range(1, pc.ndim))
                ) * cut(weights)
        # renormalize the split each step: an unbounded lo accumulation
        # would itself wrap past 2^31 (models/tc.py rationale)
        lo = lo + jnp.sum(cnt & 0x7FFF)
        hi = hi + jnp.sum(cnt >> 15) + (lo >> 15)
        lo = lo & 0x7FFF
        return hi, lo

    hi, lo = lax.fori_loop(0, steps, body, (jnp.int32(0), jnp.int32(0)))
    return jnp.stack([hi, lo])


def combine_hilo(hilo) -> int:
    """Exact host-side total from an int32 (hi, lo) 15-bit split."""
    hilo = np.asarray(hilo, np.int64)
    return int((hilo[0] << 15) + hilo[1])


def spgemm_support_bits(
    a: SpTuples,
    b: SpTuples,
    *,
    row_block: int = 4096,
) -> tuple[Array, Array]:
    """Output-support oracle: the boolean pattern of a·b as a packed
    [a.nrows, ceil(b.ncols/32)] uint32 bitmask, plus exact per-row
    nonzero counts.

    The pattern is computed as a row-blocked COUNTS product on the
    matrix unit — bool(A) @ bool(B) in bf16 (0/1 inputs are exact; f32-
    accumulated counts are exact below 2^24 ≈ any k <= 16M) — then
    thresholded and bit-packed immediately, so only one [row_block,
    ncols] dense block is ever live: the "cheap MXU work first" half of
    the masked-SpGEMM design.  Callers run the numeric pass only over
    the support (``popcount_pair_counts`` for 0/1 plus_times;
    masked gather-dot for general values).

    Only sensible where the dense operands fit (the MXU-tier envelope);
    the windowed tier uses host symbolic sizing instead at larger
    scales.
    """
    assert a.ncols == b.nrows
    m, k, n = a.nrows, a.ncols, b.ncols
    kpad = -(-k // 128) * 128
    npad = -(-n // 128) * 128
    nw = -(-n // 32)

    def support_dense(t: SpTuples, R: int, C: int) -> Array:
        # 0/1 support via scatter-ADD + clamp: duplicate-entry safe
        # (densify's unique_indices contract would be violated by
        # repeated slots) and sort-free.
        flat = jnp.where(t.valid_mask(), t.rows * C + t.cols, R * C)
        d = jnp.zeros((R * C,), jnp.float32).at[flat].add(
            1.0, mode="drop"
        )
        return jnp.minimum(d, 1.0).reshape(R, C)

    da = support_dense(a, -(-m // row_block) * row_block, kpad)
    db = support_dense(b, kpad, npad)
    da = da.astype(jnp.bfloat16)
    db = db.astype(jnp.bfloat16)
    lanes = jnp.arange(32, dtype=jnp.uint32)
    out_bits = []
    out_cnt = []
    nblocks = -(-m // row_block)
    for blk in range(nblocks):
        lo = blk * row_block
        cnt = jnp.dot(
            da[lo:lo + row_block], db, preferred_element_type=jnp.float32
        )
        live = cnt[:, :n] > 0
        out_cnt.append(jnp.sum(live, axis=1).astype(jnp.int32))
        lv = jnp.pad(live, ((0, 0), (0, nw * 32 - n)))
        packed = jnp.sum(
            lv.reshape(row_block, nw, 32).astype(jnp.uint32)
            << lanes[None, None, :],
            axis=-1, dtype=jnp.uint32,
        )
        out_bits.append(packed)
    bits = jnp.concatenate(out_bits)[:m]
    row_nnz = jnp.concatenate(out_cnt)[:m]
    return bits, row_nnz


def dense_support_nnz(dense: Array, zero, nrows: int, ncols: int) -> Array:
    """Exact nonzero count of a (possibly padded) dense block — the
    output-support size, used to size sparse extraction capacities
    exactly instead of guess-and-retry (models/mcl.py dense path)."""
    R, C = dense.shape
    mask = dense != zero
    if C != ncols:
        mask = mask & (jnp.arange(C, dtype=jnp.int32)[None, :] < ncols)
    if R != nrows:
        mask = mask & (jnp.arange(R, dtype=jnp.int32)[:, None] < nrows)
    return jnp.sum(mask).astype(jnp.int32)


# --- the MCL column select on a dense window (select before it stores) ------


def rows_kth_largest(dense: Array, ks: tuple) -> tuple:
    """Per row of a non-negative float32 ``[R, C]`` window, the
    ``k``-th largest value for every ``k`` of ``ks`` (0.0 where the row
    holds fewer than ``k`` positive cells): the thresholds of a top-k
    prune, EXACT, without a sort.

    A non-negative float's bits order as the float does, so the k-th
    largest is the largest ``t`` with ``count(row >= t) >= k``, built a
    bit at a time from the top: 31 passes, each one fused compare and
    row count over the window for all of ``ks`` at once.  A pass reads
    the window and writes ``[R]``; nothing per entry (no gather, no
    scatter, no sorted copy of the window) is on the path, which is what
    ``SpParMat.kselect``'s radix select pays 32 segment sums for on
    tuples."""
    bits = lax.bitcast_convert_type(dense, jnp.int32)

    def one_bit(i, ts):
        bit = jnp.left_shift(jnp.int32(1), 30 - i)
        out = []
        for t, k in zip(ts, ks):
            cand = t | bit
            cnt = jnp.sum(bits >= cand[:, None], axis=1, dtype=jnp.int32)
            out.append(jnp.where(cnt >= k, cand, t))
        return tuple(out)

    zeros = jnp.zeros((dense.shape[0],), jnp.int32)
    ts = lax.fori_loop(0, 31, one_bit, tuple(zeros for _ in ks))
    return tuple(lax.bitcast_convert_type(t, jnp.float32) for t in ts)


def mcl_select_rows(
    c: Array, hard: float, select: int, recover: int, rpct: float
) -> tuple[Array, Array]:
    """``MCLPruneRecoverySelect`` (ParFriends.h:186-350) on a dense
    window whose ROWS are the matrix's columns (the transposed state of
    ``models/mcl.py``), before anything is stored as tuples:

      1. cells under ``hard`` become 0 (the hard prune);
      2. a row keeps what is at least its ``select``-th largest value,
         ties kept (``SpParMat.kselect``'s threshold semantics);
      3. a row whose kept mass is under ``rpct`` of its mass after (1)
         keeps what is at least its ``recover``-th largest instead.

    Exactly ``models.mcl.mcl_prune_recovery_select``'s result on the
    same values; the thresholds come from ``rows_kth_largest`` and are
    skipped where no row holds more than ``select`` cells (then nothing
    can be cut).  Returns ``(kept window, int32[3])``: the cells above
    the prune limit (the select's candidates), the rows ``select`` cuts
    and the rows that recover."""
    c = jnp.where(c < hard, 0.0, c)
    cnt = jnp.sum(c > 0, axis=1, dtype=jnp.int32)

    def cut(c):
        s_th, r_th = rows_kth_largest(c, (select, recover))
        kept = jnp.sum(jnp.where(c >= s_th[:, None], c, 0.0), axis=1)
        need = kept < rpct * jnp.sum(c, axis=1)
        th = jnp.where(need, jnp.minimum(r_th, s_th), s_th)
        return jnp.where(c >= th[:, None], c, 0.0), jnp.sum(
            need, dtype=jnp.int32)

    c, recovered = lax.cond(
        jnp.max(cnt) > select, cut, lambda c: (c, jnp.int32(0)), c)
    counts = jnp.stack([
        jnp.sum(cnt), jnp.sum(cnt > select, dtype=jnp.int32), recovered])
    return c, counts
