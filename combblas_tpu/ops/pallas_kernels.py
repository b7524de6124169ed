"""Pallas TPU kernels for semiring-dense hot ops.

The reference's hot loops are hand-written C++ (``mtSpGEMM.h``,
``Friends.h``); on TPU most of them map best onto XLA's native
gather/sort/reduce (see ops/ and parallel/ellmat.py). The op XLA genuinely
lacks is a fused SEMIRING dense matmul: ``C = A ⊗ B`` over (min, +) or
(max, min) has no MXU lowering, and the naive jnp formulation materializes
an [m, k, n] broadcast. This Pallas kernel tiles it like a classic blocked
GEMM — A/B blocks staged in VMEM, the contraction as an in-kernel loop of
VPU adds/mins over an accumulator — giving dense-block tropical products
for APSP-style repeated squaring and dense subproblems of semiring SpGEMM.

``plus_times`` is included for completeness (it lowers to the MXU via
jnp.dot inside the kernel). The CPU tests pass ``interpret``.

The second kernel fuses what XLA keeps apart: ``pair_popcount_partials``
fetches the packed rows a list of pairs names and counts ``a & b`` where
they land, so the bit-packed harvest (``ops/spgemm.py:
popcount_pair_counts``) moves a row across HBM once, not three times.

The third builds the table those rows live in: ``pack_rows`` assembles
a packed row on the chip from the row-sorted edge list and writes it to
HBM once, where a scatter-add pays a trip to HBM a bit
(``ops/spgemm.py:pack_support_bits``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: k's folded per fori step of the tropical kernels (the lane width:
#: dynamic slices of the A block must stay 128-aligned).
_KCHUNK = 128

_FOLDS = {
    "min_plus": (jnp.minimum, jnp.add, jnp.inf),
    "max_plus": (jnp.maximum, jnp.add, -jnp.inf),
    "max_min": (jnp.maximum, jnp.minimum, -jnp.inf),
    "plus_times": (jnp.add, jnp.multiply, 0.0),
}


def _semiring_mm_kernel(a_ref, b_ref, o_ref, *, add, mul, zero, bk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, zero)

    if (add, mul) == (jnp.add, jnp.multiply):
        o_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=o_ref.dtype
        )
        return

    # Rank-1 contraction, 128 k's at a time: for each k the column
    # a[:, k] (lane-broadcast) meets the row b[k, :] (sublane-broadcast)
    # and folds into the [bm, bn] accumulator, so the only live
    # temporaries are [bm, bn]-sized.  The chunks run as a fori_loop
    # over 128-ALIGNED dynamic slices (which Mosaic accepts), the 128
    # steps inside a chunk are static.  The earlier formulation — a
    # static unroll of [bm, 8, bn] broadcast products — needed 36.8 MB
    # of scoped VMEM at the callers' 256/512/256 blocks, past the 16 MB
    # limit (v5e, jax 0.9.0 / libtpu 0.0.34).
    def chunk(c, acc):
        k0 = pl.multiple_of(c * _KCHUNK, _KCHUNK)
        a_c = a_ref[:, pl.ds(k0, _KCHUNK)]  # [bm, 128]
        b_c = b_ref[pl.ds(k0, _KCHUNK), :]  # [128, bn]
        for k in range(_KCHUNK):
            acc = add(acc, mul(a_c[:, k:k + 1], b_c[k:k + 1, :]))
        return acc

    o_ref[...] = jax.lax.fori_loop(0, bk // _KCHUNK, chunk, o_ref[...])


def semiring_matmul(
    kind: str,
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """C[i,j] = ⊕_k a[i,k] ⊗ b[k,j] for ``kind`` in {min_plus, max_plus,
    max_min, plus_times}. Dims must divide by the block sizes (pad with the
    semiring zero otherwise)."""
    add, mul, zero = _FOLDS[kind]
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        f"dims {(m, k, n)} must divide blocks {(bm, bk, bn)}"
    )
    assert kind == "plus_times" or bk % _KCHUNK == 0, (
        f"tropical kinds fold k in chunks of {_KCHUNK}; bk={bk}"
    )
    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(
        _semiring_mm_kernel, add=add, mul=mul, zero=zero, bk=bk
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
    )(a, b)


def min_plus_matmul(a, b, *, interpret: bool = False) -> jax.Array:
    """Tropical matmul — the APSP / repeated-squaring building block
    (dense-block analog of the MIN_PLUS SpGEMM)."""
    return semiring_matmul("min_plus", a, b, interpret=interpret)


# --- fused row fetch + AND-popcount (the bit-packed harvest's step) ---------

#: Lanes of a vreg.  A packed table the kernel can read holds a row as
#: whole (8, 128) tiles, ``[n, nw / LANES, LANES]`` with ``nw / LANES``
#: a multiple of 8: one contiguous copy a row (a row of the 2-D
#: ``[n, nw]`` table is one sublane of 64 tiles, and Mosaic refuses a
#: copy that is not whole tiles).
LANES = 128


def _pair_popcount_kernel(
    ii_ref, jj_ref, bi_hbm, bj_hbm, out_ref, buf_i, buf_j, sem,
    *, group: int, groups: int,
):
    """One chunk of pairs: ``groups`` groups of ``group`` pairs, each
    pair's two rows copied HBM -> VMEM (one DMA a row; both tables'
    copies of group ``g + 1`` fly while group ``g`` is counted), and
    ``population_count(a & b)`` folded over a row's tiles to ``LANES``
    partial sums a pair.  Nothing but the partial sums is written."""

    def copies(g, slot):
        for p in range(group):
            k = g * group + p
            yield pltpu.make_async_copy(
                bi_hbm.at[ii_ref[k]], buf_i.at[slot, p], sem.at[0, slot])
            yield pltpu.make_async_copy(
                bj_hbm.at[jj_ref[k]], buf_j.at[slot, p], sem.at[1, slot])

    def start(g, slot):
        for c in copies(g, slot):
            c.start()

    start(0, 0)

    def body(g, carry):
        slot = g % 2

        @pl.when(g + 1 < groups)
        def _():
            start(g + 1, 1 - slot)

        for c in copies(g, slot):
            c.wait()
        both = jax.lax.population_count(buf_i[slot] & buf_j[slot])
        out_ref[pl.ds(pl.multiple_of(g * group, group), group), :] = jnp.sum(
            both.astype(jnp.int32), axis=1)
        return carry

    jax.lax.fori_loop(0, groups, body, None)


def pair_popcount_partials(
    bits_i: jax.Array,
    bits_j: jax.Array,
    ii: jax.Array,
    jj: jax.Array,
    *,
    group: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """``int32[len(ii), LANES]`` whose row ``p`` sums to
    ``popcount(bits_i[ii[p]] & bits_j[jj[p]])``: the fetch of a pair's
    two packed rows fused with the count that reads them, so a row
    crosses HBM once and nothing of its width is written back.

    The tables (``[n, nw / LANES, LANES]``, see ``LANES``; they may be
    one array) stay in HBM; the pair ids go to scalar memory whole, so
    a call takes ONE chunk of the pair list (8,192 pairs are 64 KB),
    not the list.  ``len(ii)`` must divide by ``group``, a multiple of
    8.  VMEM: ``4 * group`` rows (2 MB at ``group=16``, 32 KB rows) and
    the output block.
    """
    (npairs,) = ii.shape
    row = bits_i.shape[1:]
    assert bits_j.shape[1:] == row and row[1:] == (LANES,), (
        bits_i.shape, bits_j.shape)
    assert group % 8 == 0 and npairs % group == 0, (npairs, group)
    kernel = functools.partial(
        _pair_popcount_kernel, group=group, groups=npairs // group)
    held = 4 * group * row[0] * LANES * 4 + 2 * npairs * LANES * 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec((npairs, LANES), lambda *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, group) + row, bits_i.dtype),
                pltpu.VMEM((2, group) + row, bits_j.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((npairs, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=held + (8 << 20)),
        name="pair_popcount",
        interpret=interpret,
    )(ii, jj, bits_i, bits_j)


# --- packed rows assembled on the chip, written once (the bit table) -------


def _pack_rows_kernel(
    off_ref, at_hbm, pos_hbm, out_ref, at_s, pos_s, landed, sem,
    *, span: int, piece: int, unroll: int, pieces: int,
):
    """One group of table rows (the grid's step; ``span`` sublanes of
    128 words): the output block is zeroed, the group's slots of the
    sorted list (``off_ref[g]`` up to ``off_ref[g + 1]``) are read from
    scalar memory, and a run of slots on one sublane (consecutive in a
    list sorted by row, then column) is OR-ed together in a register
    that is stored at every slot, so nothing is read back and the last
    store of a run holds the whole run.

    The list comes a ``piece`` at a time, pieces in order and each
    once: ``landed[0]`` pieces are in scalar memory, the next is in
    flight into the other half of the buffers (the grid's steps run in
    order and the scratch outlives a step)."""
    g = pl.program_id(0)

    def copies(b):
        at = pl.ds(pl.multiple_of(b * piece, piece), piece)
        slot = b % 2
        to = pl.ds(pl.multiple_of(slot * piece, piece), piece)
        return (
            pltpu.make_async_copy(
                at_hbm.at[at], at_s.at[to], sem.at[0, slot]),
            pltpu.make_async_copy(
                pos_hbm.at[at], pos_s.at[to], sem.at[1, slot]),
        )

    @pl.when(g == 0)
    def _():
        landed[0] = 0
        for c in copies(0):
            c.start()

    out_ref[...] = jnp.zeros_like(out_ref)
    lo, hi = off_ref[g], off_ref[g + 1]
    base = g * span
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def one_slot(k, carry):
        at, acc = carry
        to = at_s[k] - base
        pos = pos_s[k]  # word (pos >> 5) of the sublane, bit pos & 31
        acc = jnp.where(to == at, acc, 0) | jnp.where(
            lane == (pos >> 5), jnp.int32(1) << (pos & 31), 0)
        out_ref[pl.ds(to, 1), :] = jax.lax.bitcast_convert_type(
            acc, out_ref.dtype)
        return to, acc

    def one_piece(b, carry):
        @pl.when(b == landed[0])
        def _():
            for c in copies(b):
                c.wait()

            @pl.when(b + 1 < pieces)
            def _():
                for c in copies(b + 1):
                    c.start()

            landed[0] = b + 1

        # the group's slots inside this piece: a ragged head, whole
        # unrolled chunks, a ragged tail
        held = (b % 2) * piece
        first = jnp.maximum(lo - b * piece, 0)
        last = jnp.minimum(hi - b * piece, piece)
        body = jnp.minimum(-(-first // unroll) * unroll, last)
        tail = jnp.maximum(last // unroll * unroll, body)

        def chunk(j, carry):
            for u in range(unroll):
                carry = one_slot(held + j * unroll + u, carry)
            return carry

        carry = jax.lax.fori_loop(held + first, held + body, one_slot, carry)
        carry = jax.lax.fori_loop(body // unroll, tail // unroll, chunk, carry)
        return jax.lax.fori_loop(held + tail, held + last, one_slot, carry)

    jax.lax.fori_loop(
        lo // piece, -(-hi // piece), one_piece,
        (jnp.int32(0), jnp.zeros((1, LANES), jnp.int32)))


def pack_rows(
    at: jax.Array,
    pos: jax.Array,
    offsets: jax.Array,
    nrows: int,
    nw: int,
    *,
    group: int,
    piece: int,
    unroll: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """The packed table ``uint32[nrows * nw / LANES, LANES]`` (``[nrows,
    nw / LANES, LANES]`` byte for byte: a row is whole tiles), every row
    written ONCE, empty ones as zeros: slot ``k`` sets bit ``pos[k] &
    31`` of word ``pos[k] >> 5`` of the table's sublane ``at[k]`` (row
    ``at[k] // (nw / LANES)``), and sets nothing where ``pos[k] >> 5``
    is no lane (128 or more).

    ``at`` must not descend (a list sorted by row, then column; a slot
    that sets nothing carries its left neighbour's sublane).
    ``offsets`` (``int32[nrows / group + 1]``, to scalar memory whole)
    cuts the list by group of ``group`` rows: ``offsets[g]`` is the
    first slot on a sublane of row ``g * group`` or later.  The list's
    length must divide by ``piece``, ``piece`` by ``unroll``.
    """
    (slots,) = at.shape
    tiles = nw // LANES
    assert nw % (8 * LANES) == 0 and nrows % group == 0, (nrows, nw, group)
    assert slots and slots % piece == 0 and piece % unroll == 0, (
        slots, piece, unroll)
    assert offsets.shape == (nrows // group + 1,), offsets.shape
    span = group * tiles
    kernel = functools.partial(
        _pack_rows_kernel, span=span, piece=piece, unroll=unroll,
        pieces=slots // piece)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nrows // group,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec((span, LANES), lambda g, off: (g, 0)),
            scratch_shapes=[
                pltpu.SMEM((2 * piece,), jnp.int32),
                pltpu.SMEM((2 * piece,), jnp.int32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nrows * tiles, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * span * LANES * 4 + (8 << 20)),
        name="pack_rows",
        interpret=interpret,
    )(offsets, at, pos)
