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
    """Both of the cell's checks on one job; and a result is cut to
    what it stores, whatever upper bound its tier sized it by: one tile
    to its entries, the tiles of a mesh to the fullest tile's."""
    assert ref.check_digest(digest) is None
    assert ref.check_entries(*C.to_global_coo()) is None
    tiles = np.asarray(C.nnz)
    assert C.capacity == tiles.max() and tiles.sum() == digest["nnz"]
    # every tile holds its entries as a prefix, the rest padding
    stored = (np.asarray(C.rows) < C.local_rows).sum(axis=-1)
    assert np.array_equal(stored, tiles)


@pytest.fixture(scope="module")
def s8():
    n, rows, cols = _graph(8)
    return n, rows, cols, _upload(n, rows, cols), sqref.SQReference(
        n, rows, cols)


MESH = (2, 2)


@pytest.mark.parametrize("scale,blocks,grid", [
    (8, {}, (1, 1)), (8, dict(block_rows=64, block_cols=128), (1, 1)),
    (9, dict(block_rows=128, block_cols=512), (1, 1)),
    (10, dict(block_rows=512, block_cols=512), (1, 1)),
    # the mesh cell's shape in small: several row blocks and windows a
    # tile, two stages into every window's accumulator
    (8, dict(block_rows=32, block_cols=64), MESH),
    (9, dict(block_rows=64, block_cols=128), MESH),
    (9, {}, MESH),
])
def test_the_chip_s_tier_equals_the_reference_entry_for_entry(
        scale, blocks, grid):
    n, rows, cols = _graph(scale)
    ref = sqref.SQReference(n, rows, cols)
    A = _upload(n, rows, cols, grid=Grid.make(*grid))
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


@pytest.fixture(scope="module")
def s9_mesh():
    """The mesh cell's job in small: scale 9 on 2 x 2, [256, 256] tiles
    of four row blocks by two windows, under the chip's arguments."""
    n, rows, cols = _graph(9)
    A = _upload(n, rows, cols, grid=Grid.make(*MESH))
    blocks = dict(block_rows=64, block_cols=128)
    C, digest = S.spgemm_job(PLUS_TIMES, A, A, **CHIP, **blocks)
    return n, rows, cols, A, blocks, C, digest


def test_a_mesh_job_s_digest_is_the_one_tile_job_s(s9_mesh):
    n, rows, cols, _, blocks, _, digest = s9_mesh
    one = S.spgemm_job(
        PLUS_TIMES, _upload(n, rows, cols), _upload(n, rows, cols),
        **CHIP, **blocks)[1]
    assert all(np.array_equal(digest[k], one[k]) for k in (
        "nnz", "sum", "counts", "sums", "prints", "tier", "backend"))


def test_a_mesh_job_s_tiles_hold_the_product_once_under_one_capacity(
        s9_mesh):
    """Every tile is cut to the FULLEST tile's count (one static shape a
    program, and not the windows' slots: a tile's eight windows are
    sized 2^14 cells each here), the tiles' entries add up to nnz(C),
    and read in GLOBAL coordinates none repeats."""
    n, rows, cols, A, blocks, C, digest = s9_mesh
    ref = sqref.SQReference(n, rows, cols)
    plan = S.plan_windowed(PLUS_TIMES, A, A, backend="dot", **blocks)
    slots = sum(plan.chunk_caps())
    assert len(plan.chunk_caps()) == 8
    tiles = np.asarray(C.nnz)
    assert tiles.shape == MESH and tiles.sum() == ref.C.nnz == digest["nnz"]
    assert C.capacity == tiles.max() < slots
    assert tiles.min() < tiles.max()  # the emptiest tile keeps padding
    r, c, v = C.to_global_coo()
    assert len(r) == ref.C.nnz == len(np.unique(r.astype(np.int64) * n + c))
    # a tile's block of the reference, tile by tile
    lr = n // 2
    for i in range(2):
        for j in range(2):
            blk = ref.C[i * lr:(i + 1) * lr, j * lr:(j + 1) * lr]
            assert tiles[i, j] == blk.nnz
            keep = np.asarray(C.rows)[i, j] < lr
            assert np.asarray(C.vals)[i, j][keep].sum() == blk.sum()


def test_a_mesh_job_of_unequal_tiles_still_packs_and_checks():
    """An R-MAT WITHOUT the relabelling (vertices in degree order: the
    first tile holds most of the product): capacities are the heaviest
    tile's on every device, the pack cuts all four under the fullest
    tile's count, and both checks hold."""
    n, rows, cols = _graph(8)
    rank = np.empty(n, np.int32)
    rank[np.argsort(-graph.degrees(rows, n), kind="stable")] = np.arange(
        n, dtype=np.int32)
    rows, cols = rank[rows], rank[cols]
    ref = sqref.SQReference(n, rows, cols)
    A = _upload(n, rows, cols, grid=Grid.make(*MESH))
    a_tiles = np.asarray(A.nnz)
    assert a_tiles.max() > 4 * a_tiles.min()
    C, digest = S.spgemm_job(
        PLUS_TIMES, A, A, **CHIP, block_rows=32, block_cols=64)
    _held(ref, C, digest)
    tiles = np.asarray(C.nnz)
    assert tiles[0, 0] == tiles.max() == C.capacity > 1.5 * tiles[1, 1]


@pytest.mark.parametrize("schedule", [
    dict(ring=True, pipeline=True), dict(ring=True, pipeline=False),
], ids=["carousel", "carousel-serial"])
def test_the_schedule_a_mesh_job_does_not_run_gives_the_same_product(
        s9_mesh, schedule):
    """A job runs ``run_windowed``'s own schedule, the gathered one; the
    carousel, which ``scripts/sq_mesh_ladder.py`` times by the same
    steps, lays the same chunks: equal tile for tile, slot for slot."""
    n, rows, cols, A, blocks, C, digest = s9_mesh
    plan = S.plan_windowed(PLUS_TIMES, A, A, backend=CHIP["backend"], **blocks)
    C2 = S._packed(
        S.run_windowed(PLUS_TIMES, A, A, plan, mode=CHIP["mode"], **schedule),
        plan.chunk_caps())
    for a, b in ((C.rows, C2.rows), (C.cols, C2.cols), (C.vals, C2.vals),
                 (C.nnz, C2.nnz)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_mesh_job_counts_its_stages_and_names_its_exchange_and_pack(
        s9_mesh):
    n, rows, cols, A, blocks, C, digest = s9_mesh
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        C2, d2 = S.spgemm_job(PLUS_TIMES, A, A, **CHIP, **blocks)
        counters = {
            r["name"]: r["value"] for r in obs.registry.snapshot()
            if r["kind"] == "counter" and r["labels"] == {
                "tier": "windowed", "backend": "dot"}}
        tables = obs.opnames.tables()
    finally:
        obs.disable()
        obs.reset()
    assert d2["nnz"] == digest["nnz"]
    tiles = np.asarray(C.nnz)
    assert counters["spgemm.job.jobs"] == 1
    assert counters["spgemm.job.stages"] == 2
    assert counters["spgemm.job.windows"] == 8
    # one chip's stage products: two stages of eight windows, a
    # [64, 256] x [256, 128] product padded to 512 on every side
    assert counters["spgemm.job.dense_flops"] == 2 * 2 * 8 * 512 ** 3
    # a tile of each operand from the one other device of its row / column
    assert counters["spgemm.job.exchange_bytes"] == 2 * (
        4 + 12 * A.capacity)
    assert counters["spgemm.job.tile_nnz_max"] == tiles.max()
    assert counters["spgemm.job.tile_nnz_min"] == tiles.min()
    assert counters["spgemm.job.pack_capacity"] == C.capacity == tiles.max()
    found = {c for t in tables.values() for op in t.values()
             for c in op.split("/") if c.startswith("sq.")}
    assert found == set(S.SQ_SCOPES) | set(S.SQ_MESH_SCOPES)
    assert {"jit_summa_spgemm_windowed", "jit__tile_chunk_counts",
            "jit__pack_tiles", "jit_spgemm_digest"} <= set(tables)
    by_module = {
        mod: {c for op in t.values() for c in op.split("/")
              if c.startswith("sq.")} for mod, t in tables.items()}
    assert by_module["jit_summa_spgemm_windowed"] == {
        "sq.exchange", "sq.densify", "sq.dot", "sq.extract"}
    assert by_module["jit__pack_tiles"] == {"sq.pack"}


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


@pytest.mark.parametrize("grid", [(1, 1), MESH])
def test_a_second_job_compiles_nothing(grid):
    n, rows, cols = _graph(8, seed=3)
    A = _upload(n, rows, cols, grid=Grid.make(*grid))
    blocks = dict(block_rows=64 // grid[0], block_cols=128 // grid[1])
    watch = _Compiles()
    first = S.spgemm_job(PLUS_TIMES, A, A, **CHIP, **blocks)[1]
    assert watch.count > 0
    before = watch.count
    again = S.spgemm_job(PLUS_TIMES, A, A, **CHIP, **blocks)[1]
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
