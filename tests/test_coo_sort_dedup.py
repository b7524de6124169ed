"""``ops/spgemm.py:coo_sort_dedup`` orders a COO list by ``(row, col)``
and marks every repeat after the first of its run, against
``numpy.lexsort`` on the lists that could trip a sort that carries its
operands: repeats, loops, dropped slots (``rows == n``) anywhere, one
slot, one value everywhere, an ordered list.  Its two other callers
(``pack_support_bits(assume_unique=False)``, ``coo_has_duplicates``)
give what a dense numpy table gives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu.ops import spgemm as ops
from combblas_tpu.parallel import spgemm as pspgemm
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat

N = 64


def _case(name, rng):
    if name == "repeats":
        r, c = rng.integers(0, N, 400), rng.integers(0, N, 400)
        again = rng.integers(0, 400, 300)
        return np.concatenate([r, r[again]]), np.concatenate([c, c[again]])
    if name == "loops":
        d = rng.integers(0, N, 120)
        r, c = rng.integers(0, N, 200), rng.integers(0, N, 200)
        mix = rng.permutation(320)
        return np.concatenate([r, d])[mix], np.concatenate([c, d])[mix]
    if name == "sentinels-anywhere":
        r, c = rng.integers(0, N, 500), rng.integers(0, N + 1, 500)
        r[rng.integers(0, 500, 150)] = N
        return r, c
    if name == "one-slot":
        return np.array([5]), np.array([3])
    if name == "all-slots-equal":
        return np.full(257, 7), np.full(257, 9)
    if name == "already-sorted":
        r, c = rng.integers(0, N, 600), rng.integers(0, N, 600)
        order = np.lexsort((c, r))
        return r[order], c[order]
    if name == "sorted-by-column":  # the other major order
        r, c = rng.integers(0, N, 600), rng.integers(0, N, 600)
        order = np.lexsort((r, c))
        return r[order], c[order]
    if name == "descending":
        r, c = rng.integers(0, N, 600), rng.integers(0, N, 600)
        order = np.lexsort((c, r))[::-1]
        return r[order], c[order]
    if name == "one-row":
        return np.full(300, 11), rng.integers(0, 8, 300)
    if name == "one-column":
        return rng.integers(0, 8, 300), np.full(300, 11)
    raise AssertionError(name)


CASES = ("repeats", "loops", "sentinels-anywhere", "one-slot",
         "all-slots-equal", "already-sorted", "sorted-by-column",
         "descending", "one-row", "one-column")


def _expected(r, c):
    order = np.lexsort((c, r))
    r, c = r[order], c[order]
    dup = np.zeros(len(r), bool)
    dup[1:] = (r[1:] == r[:-1]) & (c[1:] == c[:-1])
    return r, c, dup


@pytest.mark.parametrize("traced", (False, True), ids=("eager", "jit"))
@pytest.mark.parametrize("name", CASES)
def test_the_list_comes_back_in_lexsort_s_order_with_its_repeats_marked(
        name, traced):
    r, c = _case(name, np.random.default_rng(len(name)))
    fn = jax.jit(ops.coo_sort_dedup) if traced else ops.coo_sort_dedup
    rows, cols, dup = fn(jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32))
    er, ec, edup = _expected(r, c)
    assert (rows.dtype, cols.dtype, dup.dtype) == (
        jnp.int32, jnp.int32, jnp.bool_)
    np.testing.assert_array_equal(np.asarray(rows), er)
    np.testing.assert_array_equal(np.asarray(cols), ec)
    np.testing.assert_array_equal(np.asarray(dup), edup)
    # True on every repeat after the first and nowhere else: the slots
    # left unmarked are the distinct pairs, once each
    first = ~np.asarray(dup)
    kept = list(zip(np.asarray(rows)[first].tolist(),
                    np.asarray(cols)[first].tolist()))
    assert sorted(kept) == sorted(set(zip(r.tolist(), c.tolist())))


def test_the_order_is_made_by_one_sort_that_carries_the_list_and_no_gather():
    """Nothing is pulled through a permutation: the traced function
    holds ONE sort, of the two lists themselves and both of them keys,
    no ``gather`` and no ``iota`` to carry (PERF.md section 6, PR 51: an
    element gather is 28 ns a slot on the chip, a sort's second key
    nothing)."""
    x = jax.ShapeDtypeStruct((1000,), jnp.int32)
    eqns = jax.make_jaxpr(ops.coo_sort_dedup)(x, x).jaxpr.eqns
    names = [e.primitive.name for e in eqns]
    assert "gather" not in names and "iota" not in names, names
    (sort,) = [e for e in eqns if e.primitive.name == "sort"]
    assert [v.aval.shape for v in sort.invars] == [(1000,), (1000,)]
    assert sort.params["num_keys"] == 2


@pytest.mark.parametrize("name", CASES)
def test_a_table_packed_from_an_unsorted_list_with_repeats_is_numpy_s(name):
    r, c = _case(name, np.random.default_rng(len(name) + 100))
    bits = ops.pack_support_bits(
        jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32), N, N)
    dense = np.zeros((N, N), bool)
    ok = (r < N) & (c < N)
    dense[r[ok], c[ok]] = True
    want = np.packbits(dense, axis=1, bitorder="little").view(np.uint32)
    np.testing.assert_array_equal(np.asarray(bits), want)


@pytest.mark.parametrize("repeated", (False, True))
def test_a_matrix_says_whether_a_tile_holds_a_repeated_entry(repeated):
    rng = np.random.default_rng(3)
    cells = rng.choice(N * N, 300, replace=False)
    r, c = cells // N, cells % N
    if repeated:
        r, c = np.append(r, r[17]), np.append(c, c[17])
    A = SpParMat.from_global_coo(
        Grid.make(2, 2), r, c, np.ones(len(r), np.float32), N, N)
    assert pspgemm.coo_has_duplicates(A) is repeated
