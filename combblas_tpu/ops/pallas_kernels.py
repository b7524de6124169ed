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
