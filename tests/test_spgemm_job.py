"""One sparse product as one job (``parallel/spgemm.py:spgemm_job``):
against the benchmark's plain reference (``chipbench/sqref.py``) entry
for entry under the tier and backend the chip runs and once under each
other tier a job can name; the digest of a C with one entry changed,
one dropped and one moved; nothing compiled by a second job; the rule
evaluated for the chip; and that neither the platform nor any
environment variable decides what a job runs."""

import numpy as np
import pytest

import jax

from chipbench import graph, sqref
from combblas_tpu import obs
from combblas_tpu.parallel import spgemm as S
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.semiring import PLUS_TIMES

#: what the cell's mix passes on (chipbench/traffic/spgemm-batch.json)
CHIP = dict(tier="windowed", backend="dot", mode="bf16")


def _graph(scale, seed=1):
    n, rows, cols, _ = graph.rmat_graph(scale, 16, seed)
    return n, rows, cols


def _upload(n, rows, cols, vals=None, grid=None):
    vals = np.ones(len(rows), np.float32) if vals is None else vals
    return SpParMat.from_global_coo(
        grid or Grid.make(1, 1), rows, cols, vals, n, n)


def _held(ref, C, digest):
    """Both of the cell's checks on one job; and a one-tile result is
    cut to what it stores, whatever upper bound its tier sized it by."""
    assert ref.check_digest(digest) is None
    assert ref.check_entries(*C.to_global_coo()) is None
    if C.grid.size == 1:
        assert C.capacity == digest["nnz"] == int(C.nnz[0, 0])


@pytest.fixture(scope="module")
def s8():
    n, rows, cols = _graph(8)
    return n, rows, cols, _upload(n, rows, cols), sqref.SQReference(
        n, rows, cols)


@pytest.mark.parametrize("scale,blocks", [
    (8, {}), (8, dict(block_rows=64, block_cols=128)),
    (9, dict(block_rows=128, block_cols=512)),
    (10, dict(block_rows=512, block_cols=512)),
])
def test_the_chip_s_tier_equals_the_reference_entry_for_entry(scale, blocks):
    n, rows, cols = _graph(scale)
    ref = sqref.SQReference(n, rows, cols)
    A = _upload(n, rows, cols)
    C, digest = S.spgemm_job(PLUS_TIMES, A, A, **CHIP, **blocks)
    assert (digest["tier"], digest["backend"]) == ("windowed", "dot")
    _held(ref, C, digest)
    assert digest["nnz"] == ref.C.nnz and digest["sum"] == ref.products
    # the product's largest entry is a degree: past bfloat16's 256 from
    # scale 9 on
    assert ref.largest > 256 or scale == 8


@pytest.mark.parametrize("job", [
    dict(tier="windowed", backend="scatter"),
    dict(tier="windowed", backend="dot", mode="f32"),
    dict(tier="scan"), dict(tier="esc"), dict(tier="mxu", mode="bf16"),
], ids=lambda j: "-".join(str(v) for v in j.values()))
def test_every_other_tier_a_job_can_name_gives_the_same_product(s8, job):
    n, rows, cols, A, ref = s8
    C, digest = S.spgemm_job(PLUS_TIMES, A, A, **job)
    assert digest["tier"] == job["tier"] in S.JOB_TIERS
    _held(ref, C, digest)


def test_a_job_on_a_mesh_and_of_two_operands(s8):
    """The digest's vectors are of GLOBAL rows and its hash of global
    columns whatever the tiling; B need not be A."""
    n, rows, cols, _, ref = s8
    A4 = _upload(n, rows, cols, grid=Grid.make(2, 2))
    C, digest = S.spgemm_job(PLUS_TIMES, A4, A4, tier="esc")
    _held(ref, C, digest)
    import scipy.sparse as sp

    m, r2, c2 = _graph(8, seed=5)
    B = _upload(m, r2, c2)
    C, digest = S.spgemm_job(
        PLUS_TIMES, _upload(n, rows, cols), B, **CHIP)
    a = sp.csr_matrix((np.ones(len(rows), np.int64), (rows, cols)), (n, n))
    b = sp.csr_matrix((np.ones(len(r2), np.int64), (r2, c2)), (n, n))
    want = (a @ b).tocsr()
    want.sort_indices()
    got, repeated = sqref.canonical(n, *C.to_global_coo())
    assert repeated == 0 and (got != want).nnz == 0
    d = sqref.digest_of(want)
    assert all(np.array_equal(digest[k], d[k]) for k in (
        "nnz", "sum", "counts", "sums", "prints"))


def _digest_of(n, r, c, v):
    nnz, hilo, counts, sums, prints = jax.device_get(S.spgemm_digest(
        _upload(n, r, c, np.asarray(v, np.float32))))
    from combblas_tpu.ops.spgemm import combine_hilo

    return {"nnz": int(nnz), "sum": combine_hilo(hilo), "counts": counts,
            "sums": sums, "prints": prints}


def test_the_digest_tells_an_entry_changed_dropped_or_moved(s8):
    n, _, _, _, ref = s8
    coo = ref.C.tocoo()
    r, c, v = coo.row.copy(), coo.col.copy(), coo.data.copy()
    assert ref.check_digest(_digest_of(n, r, c, v)) is None
    k = len(r) // 3
    changed = v.copy()
    changed[k] += 1
    bad = ref.check_digest(_digest_of(n, r, c, changed))
    assert "sum" in bad and "sums differs in 1 rows" in bad
    assert "prints differs in 1 rows" in bad and "counts" not in bad
    keep = np.arange(len(r)) != k
    bad = ref.check_digest(_digest_of(n, r[keep], c[keep], v[keep]))
    assert "nnz" in bad and "counts differs in 1 rows" in bad
    # moved along its row to a column the row does not hold: only the
    # fingerprint can tell
    row = ref.C[r[k]].indices
    free = next(j for j in range(n) if j not in row)
    moved = c.copy()
    moved[k] = free
    bad = ref.check_digest(_digest_of(n, r, moved, v))
    assert bad.startswith("prints differs in 1 rows")
    assert ref.check_entries(r, moved, v).startswith(
        f"{len(r)} entries, the reference has {len(r)}; 2 coordinates")
    # moved to another row: the counts tell
    other = r.copy()
    other[k] = (r[k] + 1) % n
    assert "counts differs in 2 rows" in ref.check_digest(
        _digest_of(n, other, moved, v))
    assert "hold another value" in ref.check_entries(r, c, changed)
    assert "repeat a coordinate" in ref.check_entries(
        np.append(r, r[k]), np.append(c, c[k]), np.append(v, 0))


def test_a_result_is_cut_at_its_chunks_prefixes():
    """``_packed``: chunks of static capacities, each holding its
    entries as a prefix; what is not a prefix is refused, not dropped."""
    n = 8
    lay = [([0, 1, 2], 4), ([], 3), ([3, 3, 5, 7, 7], 5)]
    rows = np.concatenate([r + [n] * (cap - len(r)) for r, cap in lay])
    cols = np.where(rows < n, np.arange(len(rows)) % n, n)
    C = SpParMat(
        rows=jax.numpy.asarray(rows, jax.numpy.int32)[None, None],
        cols=jax.numpy.asarray(cols, jax.numpy.int32)[None, None],
        vals=jax.numpy.arange(len(rows), dtype=jax.numpy.float32)[
            None, None],
        nnz=jax.numpy.asarray([[8]], jax.numpy.int32), nrows=n, ncols=n,
        grid=Grid.make(1, 1))
    out = S._packed(C, (4, 3, 5))
    keep = rows < n
    assert out.capacity == 8 and np.array_equal(
        np.asarray(out.rows)[0, 0], rows[keep])
    assert np.array_equal(np.asarray(out.cols)[0, 0], cols[keep])
    assert np.array_equal(
        np.asarray(out.vals)[0, 0], np.arange(12, dtype=np.float32)[keep])
    # the same slots cut elsewhere: the second chunk starts with padding
    with pytest.raises(AssertionError, match="prefix"):
        S._packed(C, (4, 5, 3))


def test_the_sum_of_the_digest_is_exact_past_32_bits():
    n = 96
    r, c = np.divmod(np.arange(n * 64), 64)
    v = np.full(len(r), 1 << 24, np.int64)
    v[::7] = (1 << 24) - 3
    d = _digest_of(n, r, c, v)
    assert d["sum"] == int(v.sum()) > 1 << 36
    assert d["nnz"] == len(r) and (d["counts"] == 64).all()
    assert np.array_equal(
        d["sums"].astype(np.int64), np.bincount(r, weights=v).astype(
            np.int64))


class _Compiles:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        self.count += event == self.EVENT


def test_a_second_job_compiles_nothing():
    n, rows, cols = _graph(8, seed=3)
    A = _upload(n, rows, cols)
    watch = _Compiles()
    first = S.spgemm_job(
        PLUS_TIMES, A, A, **CHIP, block_rows=64, block_cols=128)[1]
    assert watch.count > 0
    before = watch.count
    again = S.spgemm_job(
        PLUS_TIMES, A, A, **CHIP, block_rows=64, block_cols=128)[1]
    assert watch.count == before
    assert again["nnz"] == first["nnz"] and np.array_equal(
        again["prints"], first["prints"])


#: the deployment's graph by scale, counted on the host (ISSUE 40's
#: table, ``sqref.SQReference.products``)
PRODUCTS = {14: 1.566e8, 15: 4.432e8, 16: 1.244e9}


@pytest.mark.parametrize("scale", sorted(PRODUCTS))
def test_the_rule_evaluated_for_the_chip_picks_the_mix_s_tier(scale):
    """``choose_tier_from_counts`` under the chip's backend, on counts
    alone: what the cell's mix passes on is what the library's own rule
    picks at every scale the configuration may ship."""
    n = 1 << scale
    assert S.JOB_BACKEND == CHIP["backend"]
    assert S.choose_tier_from_counts(
        PLUS_TIMES, n, n * n, 1, PRODUCTS[scale], S.JOB_BACKEND,
        k_dim=n, n_dim=n) == CHIP["tier"]


def test_a_job_with_no_tier_routes_by_the_rule_inside_it(s8):
    n, rows, cols, A, ref = s8
    # small tiles of unique entries: the rule's first rung
    C, digest = S.spgemm_job(PLUS_TIMES, A, A)
    assert digest["tier"] == "mxu"
    _held(ref, C, digest)
    # a repeated entry takes the densifying rung away (the windowed
    # tier's combining scatter absorbs it: A[i, j] = 2)
    k = 11
    dup = _upload(n, np.append(rows, rows[k]), np.append(cols, cols[k]))
    assert S.spgemm_job(PLUS_TIMES, dup, dup)[1]["tier"] == "windowed"


def test_neither_the_platform_nor_the_environment_decides(s8):
    """The tier-1 tests run on a CPU, whose platform default is the
    scatter backend: a job runs what its arguments say, shown by its
    span's labels (no environment variable routes a product any more:
    ``tests/test_spgemm_routing.py`` holds that, a name a case)."""
    n, rows, cols, A, ref = s8
    assert jax.default_backend() == "cpu"
    assert S.resolve_spgemm_backend() == "scatter"
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        C, digest = S.spgemm_job(
            PLUS_TIMES, A, A, **CHIP, block_rows=64, block_cols=128)
        spans = {s["path"]: s for s in obs.spans()}
        counters = {
            (r["name"], tuple(sorted(r["labels"].items()))): r["value"]
            for r in obs.registry.snapshot() if r["kind"] == "counter"}
        tables = obs.opnames.tables()
    finally:
        obs.disable()
        obs.reset()
    _held(ref, C, digest)
    job = spans["spgemm.job"]
    assert job["attrs"] == {
        "sr": "plus_times", "backend": "dot", "tier": "windowed"}
    parts = [spans[f"spgemm.job/{p}"]["wall_s"]
             for p in ("symbolic", "numeric", "digest")]
    assert 0 < sum(parts) <= job["wall_s"]
    lab = (("backend", "dot"), ("tier", "windowed"))
    assert counters[("spgemm.job.jobs", lab)] == 1
    assert counters[("spgemm.job.nnz_out", lab)] == ref.C.nnz
    assert counters[("spgemm.job.products", lab)] == ref.products
    assert counters[("spgemm.job.windows", lab)] == 4 * 2
    assert counters[("spgemm.job.windows_skipped", lab)] == 0
    assert counters[("spgemm.job.dense_flops", lab)] == (
        8 * 2 * 512 * 512 * 512)
    # no plan came from a store, a probe or the environment
    assert not any(name.startswith(("spgemm.auto.", "tuner."))
                   for name, _ in counters)
    # the first traced job published every program's scopes, a row
    # block's launches one table each
    found = {c for t in tables.values() for op in t.values()
             for c in op.split("/") if c.startswith("sq.")}
    assert found == set(S.SQ_SCOPES)
    assert {f"jit__windowed_block_local_dot#{k}" for k in range(4)} <= set(
        tables)


def test_a_job_never_retries(s8, monkeypatch):
    """A capacity below the product doubles and runs again in
    ``spgemm_scan``; a job's capacities are symbolic upper bounds, its
    numeric phase runs once, and an overflow is an error."""
    import inspect

    n, rows, cols, A, ref = s8
    assert "max_retries" not in inspect.signature(S.spgemm_job).parameters
    calls = []
    real = S.summa_spgemm_scan

    def counted(*a, **kw):
        calls.append(kw["out_capacity"])
        return real(*a, **kw)

    monkeypatch.setattr(S, "summa_spgemm_scan", counted)
    C, digest = S.spgemm_job(PLUS_TIMES, A, A, tier="scan")
    assert len(calls) == 1 and calls[0] >= ref.C.nnz
    _held(ref, C, digest)
    monkeypatch.setattr(
        S, "summa_capacities", lambda A, B: (1 << 20, ref.C.nnz // 2))
    with pytest.raises(AssertionError, match="overflowed its symbolic"):
        S.spgemm_job(PLUS_TIMES, A, A, tier="scan")
    assert len(calls) == 2


@pytest.fixture
def grain(monkeypatch):
    """``grain(cells)`` sets ``SPARSIFY_GROUP_CELLS`` and returns the
    list of ``[G, L]`` shapes whose prefixes were laid end to end since.
    The grain is read when a program is traced: nothing traced before
    may serve these jobs, and nothing traced here a later test."""
    from combblas_tpu.ops import spgemm as ops

    laid = []
    real = ops._lay_prefixes

    def counted(key, vals):
        laid.append(key.shape)
        return real(key, vals)

    def set_grain(cells):
        monkeypatch.setattr(ops, "SPARSIFY_GROUP_CELLS", cells)
        monkeypatch.setattr(ops, "_lay_prefixes", counted)
        jax.clear_caches()
        return laid

    yield set_grain
    jax.clear_caches()


def test_a_job_whose_windows_are_sorted_by_row_groups(s8, grain):
    """The cell's tier and backend with several groups a window (a
    [512, 512] window in 16 groups of 32 rows): the digest and every
    entry equal the reference's."""
    n, rows, cols, A, ref = s8
    laid = grain(1 << 14)
    C, digest = S.spgemm_job(
        PLUS_TIMES, A, A, **CHIP, block_rows=128, block_cols=128)
    _held(ref, C, digest)
    # one trace serves both row blocks: two windows, 16 groups each
    assert laid == [(16, 1 << 14)] * 2


@pytest.mark.parametrize("backend,cells,windows,groups", [
    ("dot", 1 << 18, 4, 4), ("dot", 1 << 14, 4, 4 * 16),
    ("scatter", 1 << 14, 2, 2 * 2),
])
def test_a_job_counts_the_row_groups_it_sorted(
        s8, grain, backend, cells, windows, groups):
    """``spgemm.job.extract_groups``: the launched windows' groups, from
    the plan's static shapes ([512, 512] under ``dot``, [128, 256] under
    ``scatter``); ``windows`` where every window took the flat sort."""
    n, rows, cols, A, ref = s8
    laid = grain(cells)
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        C, digest = S.spgemm_job(
            PLUS_TIMES, A, A, tier="windowed", backend=backend,
            mode="bf16", block_rows=128, block_cols=128)
        counters = {
            r["name"]: r["value"] for r in obs.registry.snapshot()
            if r["kind"] == "counter" and r["labels"] == {
                "tier": "windowed", "backend": backend}}
    finally:
        obs.disable()
        obs.reset()
    _held(ref, C, digest)
    assert counters["spgemm.job.windows"] == windows
    assert counters["spgemm.job.extract_groups"] == groups
    assert bool(laid) == (groups > windows)
