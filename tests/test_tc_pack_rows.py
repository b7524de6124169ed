"""The whole-tile bit table is the same table whoever writes it:
``pack_support_bits(row_tiles=True)`` through the on-chip pack
(``ops/pallas_kernels.py:pack_rows``, interpreted here: a CPU has no
Mosaic) against the scatter-add every other caller keeps, bit for bit,
on the lists that could trip a kernel that walks a row-sorted list a
group of rows and a piece of the list at a time.  Small shapes: 32,768
columns are the fewest whose packed row is whole tiles; the group is cut
to 8 rows and the piece to 256 slots so that a few hundred slots cross
both."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu import obs
from combblas_tpu.models import tc
from combblas_tpu.ops import spgemm as ops
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat

N = 32768  # columns: 1,024 words a row, eight (8, 128) tiles' lanes
ROWS = 48  # six groups of 8
GROUP, PIECE = 8, 256
SLOTS = 1536  # every case's list, padded with dropped slots: one trace


def _packer(mode):
    """``pack_support_bits`` as ``models/tc.py`` calls it, traced where
    ``_kernel_mode`` says ``mode`` (the path is read at trace time)."""
    def pack(r, c):
        with mock.patch.object(ops, "_kernel_mode", lambda: mode), \
                mock.patch.object(ops, "PACK_GROUP", GROUP), \
                mock.patch.object(ops, "PACK_PIECE", PIECE):
            return ops.pack_support_bits(
                r, c, ROWS, N, assume_unique=True, row_tiles=True)
    return jax.jit(pack)


@pytest.fixture(scope="module")
def packers():
    return _packer("interpret"), _packer(None)


def _sorted(r, c):
    order = np.lexsort((c, r))
    return np.asarray(r)[order], np.asarray(c)[order]


def _case(name, rng):
    """``(rows, cols)``: sorted by row, then column; a dropped slot is
    marked at row ``ROWS`` where it lies."""
    if name in ("empty-list", "no-slot-at-all"):
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    if name == "rows-without-an-edge":
        # none in rows 0-9 (a whole group and more), 20-30 and 41-47
        r = rng.choice([10, 11, 15, 16, 19, 31, 32, 40], 300)
        return _sorted(r, rng.integers(0, N, 300))
    if name == "a-hub-longer-than-a-piece":
        hub = rng.choice(N, 3 * PIECE + 17, replace=False)
        r = np.concatenate([np.full(len(hub), 21), rng.integers(0, ROWS, 200)])
        return _sorted(r, np.concatenate([hub, rng.integers(0, N, 200)]))
    if name == "a-run-across-a-group-boundary":
        # rows 6-9 and 15-16 full of slots: groups 0|1 and 1|2 meet
        # inside a piece and inside an unrolled chunk
        r = rng.choice([6, 7, 8, 9, 15, 16], 700)
        return _sorted(r, rng.integers(0, N, 700))
    if name == "loops-and-repeats-inside":
        r = rng.integers(0, ROWS, 500)
        c = np.where(rng.random(500) < 0.5, rng.integers(0, ROWS, 500),
                     rng.integers(0, N, 500))
        r, c = _sorted(np.concatenate([r, r[:150], np.arange(ROWS)]),
                       np.concatenate([c, c[:150], np.arange(ROWS)]))
        dup = np.concatenate(
            [[False], (r[1:] == r[:-1]) & (c[1:] == c[:-1])])
        assert dup.sum() >= 150 and (r == c).sum() >= ROWS
        return np.where(dup | (r == c), ROWS, r), c
    if name == "the-last-row":
        r = np.full(90, ROWS - 1)
        return _sorted(r, rng.choice(N, 90, replace=False))
    if name == "every-bit-of-a-word":
        # words 0, 127 (a sublane's ends), 128 and the row's last, whole
        words = np.array([0, 127, 128, N // 32 - 1])
        c = (words[:, None] * 32 + np.arange(32)).ravel()
        return _sorted(np.concatenate([np.full(128, 13), np.full(128, 47)]),
                       np.concatenate([c, c]))
    raise AssertionError(name)


CASES = ("empty-list", "no-slot-at-all", "rows-without-an-edge",
         "a-hub-longer-than-a-piece", "a-run-across-a-group-boundary",
         "loops-and-repeats-inside", "the-last-row", "every-bit-of-a-word")


@pytest.mark.parametrize("case", CASES)
def test_the_on_chip_pack_writes_the_table_the_scatter_add_writes(
        case, packers):
    on_chip, scatter = packers
    r, c = _case(case, np.random.default_rng(CASES.index(case)))
    if case != "empty-list":  # that one keeps its zero length
        pad = SLOTS - len(r)
        r = np.concatenate([r, np.full(pad, ROWS)])
        c = np.concatenate([c, np.zeros(pad, np.int64)])
    r, c = jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32)
    got, want = np.asarray(on_chip(r, c)), np.asarray(scatter(r, c))
    assert got.shape == want.shape == (ROWS, N // 32 // 128, 128)
    assert got.dtype == want.dtype == np.uint32
    assert np.array_equal(got, want)
    # and both are the definition's, on the host: one bit a kept slot
    r, c = np.asarray(r), np.asarray(c)
    kept = r < ROWS
    table = np.zeros((ROWS, N), bool)
    table[r[kept], c[kept]] = True
    assert np.array_equal(
        np.unpackbits(got.reshape(ROWS, -1).view(np.uint8), axis=1,
                      bitorder="little").astype(bool), table)
    assert (table.sum() == 0) == (case in ("empty-list", "no-slot-at-all"))
    if case == "every-bit-of-a-word":
        assert (got == 0xFFFFFFFF).sum() == 8


def test_unsorted_input_is_sorted_first_as_the_scatter_add_sorts_it():
    """Without ``assume_unique`` the list comes in any order and with
    repeats: ``coo_sort_dedup`` gives the kernel the order it needs."""
    rng = np.random.default_rng(3)
    r = jnp.asarray(rng.integers(0, ROWS + 3, 900), jnp.int32)
    c = jnp.asarray(rng.integers(0, N + 40, 900), jnp.int32)
    r, c = jnp.concatenate([r, r[:200]]), jnp.concatenate([c, c[:200]])
    want = ops.pack_support_bits(r, c, ROWS, N, row_tiles=True)
    with mock.patch.object(ops, "_kernel_mode", lambda: "interpret"):
        got = ops.pack_support_bits(r, c, ROWS, N, row_tiles=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(jax.lax.population_count(want).astype(jnp.int32).sum()) > 800


def _job(A):
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        triple = tc.tc_job(A)
        packs = [(r["labels"], r["value"]) for r in obs.registry.snapshot()
                 if r["name"] == "models.tc.pack"]
    finally:
        obs.disable()
        obs.reset()
    return triple, packs


def test_a_job_through_the_on_chip_pack_is_the_same_triple(monkeypatch):
    """``tc_job`` at n = 32,768 where a kernel runs packs its table on
    the chip and says so; where none runs it scatters; the triple is
    one."""
    rng = np.random.default_rng(17)
    ids = np.sort(rng.choice(N, 300, replace=False)).astype(np.int32)
    r, c = ids[rng.integers(0, 300, 2500)], ids[rng.integers(0, 300, 2500)]
    rows, cols = np.concatenate([r, c, r[:99]]), np.concatenate([c, r, c[:99]])
    A = SpParMat.from_global_coo(
        Grid.make(1, 1), rows, cols, np.ones(len(rows), np.float32), N, N)
    scattered, packs = _job(A)
    assert packs == [({"path": "scatter"}, 1)]
    monkeypatch.setattr(ops, "_kernel_mode", lambda: "interpret")
    jax.clear_caches()
    try:
        text = tc.tc_edgeharvest_bits.lower(A.rows, A.cols, n=N).as_text()
        assert f"tensor<{N * 8}x128xui32>" in text  # the kernel's output
        on_chip, packs = _job(A)
    finally:
        jax.clear_caches()
    assert packs == [({"path": "rows"}, 1)]
    assert on_chip == scattered and on_chip[0] > 0


def test_the_job_hands_the_pack_the_order_the_kernel_needs(monkeypatch):
    """``assume_unique`` on the whole-tile path promises rows ascending
    and columns ascending within a row over the slots that count:
    ``_tc_edge_harvest_bits`` passes ``coo_sort_dedup``'s order with
    its loops and repeats marked at row ``n`` where they lie."""
    rng = np.random.default_rng(23)
    n = 256
    r, c = rng.integers(0, n, 3000), rng.integers(0, n, 3000)
    seen = []
    real = ops.pack_support_bits

    def spy(rows, cols, *a, **k):
        seen.append((np.asarray(rows), np.asarray(cols), k))
        return real(rows, cols, *a, **k)

    monkeypatch.setattr(tc, "pack_support_bits", spy)
    tc._tc_edge_harvest_bits(
        jnp.asarray(np.concatenate([r, c]), jnp.int32),
        jnp.asarray(np.concatenate([c, r]), jnp.int32), n=n)
    (rows, cols, kwargs), = seen
    assert kwargs["assume_unique"] is True
    kept = rows < n
    key = rows[kept].astype(np.int64) * n + cols[kept]
    assert (np.diff(key) > 0).all()  # ascending, and no repeat left
    inside = np.flatnonzero(~kept)
    assert len(inside) > 100 and inside.min() < np.flatnonzero(kept).max()
