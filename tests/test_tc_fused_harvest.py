"""The harvest's two steps give one count: ``popcount_pair_counts`` over
whole-tile tables through the fused kernel
(``ops/pallas_kernels.py:pair_popcount_partials``, interpreted here: a
CPU has no Mosaic) against the ``jnp`` loop over the same words as plain
rows, and which tables take which step.  Small shapes: 32,768 columns
are the fewest whose packed row is whole tiles (1,024 words), so the
tables here are a few rows of that width."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from combblas_tpu import obs
from combblas_tpu.models import tc
from combblas_tpu.ops import pallas_kernels
from combblas_tpu.ops import spgemm as ops
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat

N = 32768  # 1,024 words a row: eight (8, 128) tiles' lanes, one tile
ROWS = 96  # rows of the rectangular tables
CHUNK = 64  # the pair list's padding here (8,192 in a job)


@pytest.fixture
def interpreted(monkeypatch):
    """A backend that runs the kernel, as a TPU would compiled: the
    path is read from ``_kernel_mode`` at trace time, so the caches go
    with it."""
    monkeypatch.setattr(ops, "_kernel_mode", lambda: "interpret")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _coo(rng, nrows, ncols, m):
    r, c = rng.integers(0, nrows, m), rng.integers(0, ncols, m)
    return jnp.asarray(r, jnp.int32), jnp.asarray(c, jnp.int32)


def _tables(rng, ncols, distinct):
    """``(rows_i, rows_j, tiles_i, tiles_j)``: the same words as
    ``[ROWS, nw]`` and as ``[ROWS, nw / 128, 128]``."""
    a = _coo(rng, ROWS, ncols, ROWS * ncols // 20)
    b = _coo(rng, ROWS, ncols, ROWS * ncols // 30) if distinct else a
    flat = [ops.pack_support_bits(r, c, ROWS, ncols) for r, c in (a, b)]
    tiled = [ops.pack_support_bits(r, c, ROWS, ncols, row_tiles=True)
             for r, c in (a, b)]
    for f, t in zip(flat, tiled):
        assert t.shape == (ROWS, ncols // 32 // 128, 128)
        assert np.array_equal(np.asarray(t).reshape(f.shape), np.asarray(f))
    return (*flat, *tiled)


@pytest.mark.parametrize("nrows,ncols", [(40, N), (24, 3 * N)])
def test_whole_tile_rows_hold_the_words_plain_rows_hold(nrows, ncols):
    """``row_tiles`` scatters into ``[nrows * tiles, 128]`` and
    reshapes: the words are the plain table's, with repeats masked and
    out-of-range slots dropped as it drops them; a row that is no whole
    number of tiles is refused."""
    rng = np.random.default_rng(ncols)
    m = nrows * ncols // 16
    r = jnp.asarray(rng.integers(0, nrows + 3, m), jnp.int32)
    c = jnp.asarray(rng.integers(0, ncols + 40, m), jnp.int32)
    flat = ops.pack_support_bits(r, c, nrows, ncols)
    tiled = ops.pack_support_bits(r, c, nrows, ncols, row_tiles=True)
    assert tiled.shape == (nrows, ncols // 32 // 128, 128)
    assert np.array_equal(np.asarray(tiled).reshape(flat.shape), flat)
    assert int(jax.lax.population_count(flat).astype(jnp.int32).sum()) > m // 2
    with pytest.raises(AssertionError):
        ops.pack_support_bits(r, c, nrows, 4096, row_tiles=True)


def _pairs(case, rng):
    """``(ii, jj, weights, count)``, padded to ``CHUNK``."""
    m = {"two-tables": 3 * CHUNK, "weights-with-zeros": 2 * CHUNK,
         "ragged-count": 2 * CHUNK + 37, "count-zero": 0,
         "a-run-on-one-row": 2 * CHUNK - 5,
         "smallest-shape": CHUNK}[case]
    ii = rng.integers(0, ROWS, m)
    jj = rng.integers(0, ROWS, m)
    w = np.ones(m, np.int64)
    if case == "a-run-on-one-row":
        ii[5:5 + CHUNK + 9] = 77  # a run longer than a step and a group
        jj[:16] = 3
    if case == "weights-with-zeros":
        w[rng.random(m) < 0.4] = 0
    pad = -m % CHUNK + (CHUNK if case == "count-zero" else 0)
    z = np.zeros(pad, np.int64)
    count = None if case == "weights-with-zeros" else m
    return tuple(jnp.asarray(np.concatenate([a, z]), jnp.int32)
                 for a in (ii, jj, w)) + (count,)


CASES = ("two-tables", "weights-with-zeros", "ragged-count", "count-zero",
         "a-run-on-one-row", "smallest-shape")


@pytest.mark.parametrize("case", CASES)
def test_the_fused_step_counts_what_the_jnp_loop_counts(
        case, interpreted, monkeypatch):
    rng = np.random.default_rng(CASES.index(case))
    ncols = N if case == "smallest-shape" else 2 * N
    fi, fj, ti, tj = _tables(rng, ncols, distinct=case == "two-tables")
    ii, jj, w, count = _pairs(case, rng)
    if count is not None:
        count = jnp.int32(count)
    # a group smaller than the step, so a step is several groups and
    # both buffers turn over; the constant is read when a step is traced
    monkeypatch.setattr(ops, "HARVEST_GROUP", 8)
    launched = []
    real = pallas_kernels.pair_popcount_partials
    monkeypatch.setattr(
        pallas_kernels, "pair_popcount_partials",
        lambda *a, **k: launched.append(k) or real(*a, **k))
    fused = ops.popcount_pair_counts(
        ti, tj, ii, jj, w, chunk=CHUNK, count=count)
    assert launched == [{"group": 8, "interpret": True}]
    del launched[:]
    plain = ops.popcount_pair_counts(
        fi, fj, ii, jj, w, chunk=CHUNK, count=count)
    assert launched == []  # rows that are no whole tiles: the jnp loop
    assert ops.combine_hilo(fused) == ops.combine_hilo(plain)
    # and both are the definition's, on the host
    walked = len(ii) if count is None else int(count)
    both = np.asarray(fi)[np.asarray(ii)] & np.asarray(fj)[np.asarray(jj)]
    each = np.unpackbits(both.view(np.uint8), axis=1).sum(axis=1)
    want = int((each * np.asarray(w))[:-(-walked // CHUNK) * CHUNK].sum())
    assert ops.combine_hilo(fused) == want
    assert (want == 0) == (case == "count-zero")


@pytest.mark.parametrize("n,mode,path", [
    (32768, "compiled", "fused"), (65536, "interpret", "fused"),
    (32768, None, "jnp"), (36864, "compiled", "jnp"), (4096, "compiled", "jnp"),
    (256, "compiled", "jnp"), (1 << 18, "compiled", "fused"),
    (1 << 18, None, "jnp")])
def test_the_path_is_read_from_the_backend_and_the_word_axis(
        n, mode, path, monkeypatch):
    monkeypatch.setattr(ops, "_kernel_mode", lambda: mode)
    assert ops.harvest_path(-(-n // 32)) == path


def _few_vertices(rng, edges):
    """A symmetric edge list among 300 of the ``N`` vertices, spread
    over the whole range (so a row's bits lie in every tile), with
    triangles among them."""
    ids = np.sort(rng.choice(N, 300, replace=False)).astype(np.int32)
    r, c = ids[rng.integers(0, 300, edges)], ids[rng.integers(0, 300, edges)]
    return np.concatenate([r, c]), np.concatenate([c, r])


def test_this_backend_runs_no_kernel_so_a_job_takes_the_jnp_loop():
    """A CPU: ``_kernel_mode`` is None whatever the shape, the table is
    plain rows, and the job's counter says which loop ran."""
    assert ops._kernel_mode() is None
    rows, cols = _few_vertices(np.random.default_rng(5), 3000)
    A = SpParMat.from_global_coo(
        Grid.make(1, 1), rows, cols, np.ones(len(rows), np.float32), N, N)
    text = tc.tc_edgeharvest_bits.lower(A.rows, A.cols, n=N).as_text()
    assert f"tensor<{N}x1024xui32>" in text and "x8x128xui32" not in text
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        triangles, pairs, _ = tc.tc_job(A)
        steps = [(r["labels"], r["value"]) for r in obs.registry.snapshot()
                 if r["name"] == "models.tc.harvest_steps"]
    finally:
        obs.disable()
        obs.reset()
    assert triangles > 0
    assert steps == [({"path": "jnp"}, pairs // tc.HARVEST_CHUNK)]


def test_a_job_through_the_fused_step_is_the_same_triple(
        interpreted, monkeypatch):
    """``_tc_edge_harvest_bits`` at n = 32,768 where a kernel runs: the
    table is packed as whole-tile rows by the scatter itself, and the
    triple is the ``jnp`` loop's."""
    r, c = _few_vertices(np.random.default_rng(11), 2500)
    rows = jnp.asarray(np.concatenate([r, r[:99]]))  # with repeats
    cols = jnp.asarray(np.concatenate([c, c[:99]]))
    job = jax.jit(tc._tc_edge_harvest_bits, static_argnames=("n", "chunk"))
    text = job.lower(rows, cols, n=N, chunk=256).as_text()
    assert f"tensor<{N}x8x128xui32>" in text
    assert f"tensor<{N}x1024xui32>" not in text
    fused = jax.device_get(job(rows, cols, n=N, chunk=256))
    monkeypatch.setattr(ops, "_kernel_mode", lambda: None)
    jax.clear_caches()
    plain = jax.device_get(job(rows, cols, n=N, chunk=256))
    assert [int(v) for v in fused[1:]] == [int(v) for v in plain[1:]]
    assert ops.combine_hilo(fused[0]) == ops.combine_hilo(plain[0]) > 0
    assert fused[1] % 256 == 0 and 0 < fused[1] - fused[2] < 256
    assert fused[1] // 256 > 4  # several steps of several groups
