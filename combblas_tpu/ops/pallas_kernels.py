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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

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
