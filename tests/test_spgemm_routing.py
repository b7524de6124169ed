"""A product's kernel is chosen in one place (PR 43): from an argument,
or from the operands' counts by ``choose_tier_from_counts``, the rule
``spgemm_auto`` and ``spgemm_job`` both end in.

* the rule as a table, expected tiers written out;
* ``spgemm_auto(tier=None)`` and ``spgemm_job(tier=None)`` under one
  backend choose the same tier and give the same C;
* the eleven environment names that re-routed a product until PR 43,
  one case a name: set to a contrary value each leaves every choice
  what it is unset;
* ``resolve_spmm_backend`` and ``spgemm3d``'s defaults as tables.
"""

import json
import math

import jax
import numpy as np
import pytest

from combblas_tpu import obs
from combblas_tpu.parallel import mesh3d
from combblas_tpu.parallel import spgemm as S
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.mesh3d import Grid3D, SpParMat3D, spgemm3d
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.parallel.spmm import resolve_spmm_backend
from combblas_tpu.semiring import (
    MAX_MIN,
    MIN_PLUS,
    PLUS_TIMES,
    SELECT2ND_MAX,
    Semiring,
)

#: No scatter combiner and no dense kernel: every tier but the sorts'
#: is closed to it.
GENERIC = Semiring(
    name="plus_times_generic", add=lambda x, y: x + y,
    mul=lambda a, x: a * x, zero_fn=lambda dt: 0,
    one_fn=lambda dt: 1, add_kind="generic",
)

SRS = {
    "plus_times": PLUS_TIMES,      # a scatter combiner and a dense kernel
    "min_plus": MIN_PLUS,          # likewise
    "select2nd_max": SELECT2ND_MAX,  # a scatter combiner, no dense kernel
    "generic": GENERIC,            # neither
}

# --- the rule as a table ------------------------------------------------------

#: the rule's line, cells a multiply: a power of two, so that a count AT
#: it is a whole number
LINE = int(S.WINDOWED_MAX_CELLS_PER_FLOP)
#: the multiplies that put 2^30 cells at the line
AT = float((1 << 30) // LINE)

#: (semiring, max tile dim, tile cells, grid rows, multiplies, backend,
#: k_dim, n_dim, allow_mxu) -> tier.  The limits the rows stand on both
#: sides of: the whole-tile rung's 8,192; the windowed tier's 2^33 cells
#: a tile and ``LINE`` cells a multiply; a dot panel of 2^27 cells
#: (padded k x a window of 512 columns, or of n / 32 where that is
#: wider).
RULE = [
    # the whole-tile rung, on both sides of 8,192
    ("plus_times", 8192, 8192 * 8192, 1, 1e9, "dot", 8192, 8192, True, "mxu"),
    ("plus_times", 8192, 8192 * 8192, 1, 1e9, "scatter", 8192, 8192, True,
     "mxu"),
    ("plus_times", 8193, 8193 * 8193, 1, 1e9, "dot", 8193, 8193, True,
     "windowed"),
    ("plus_times", 8193, 8193 * 8193, 1, 1e9, "scatter", 8193, 8193, True,
     "windowed"),
    ("min_plus", 4096, 4096 * 4096, 1, 1e8, "dot", 4096, 4096, True, "mxu"),
    ("min_plus", 16384, 1 << 28, 1, 1e8, "dot", 16384, 16384, True,
     "windowed"),
    # repeated entries take the whole-tile rung away
    ("plus_times", 8192, 8192 * 8192, 1, 1e9, "dot", 8192, 8192, False,
     "windowed"),
    ("plus_times", 8192, 8192 * 8192, 1, 1e9, "scatter", 8192, 8192, False,
     "windowed"),
    ("plus_times", 64, 64 * 64, 1, 2.0, "dot", 64, 64, False, "scan"),
    # 2^33 cells a tile: at it and one past it
    ("plus_times", 131072, 1 << 33, 1, 2.0 ** 29, "dot", 16384, 65536, True,
     "windowed"),
    ("plus_times", 131072, 1 << 33, 1, 2.0 ** 29, "scatter", 16384, 65536,
     True, "windowed"),
    ("plus_times", 131072, (1 << 33) + 1, 1, 2.0 ** 30, "dot", 16384, 65536,
     True, "scan"),
    ("plus_times", 131072, (1 << 33) + 1, 1, 2.0 ** 30, "scatter", 16384,
     65536, True, "scan"),
    # the line: at it and one multiply short of it
    ("plus_times", 32768, 1 << 30, 1, AT, "dot", 32768, 32768, True,
     "windowed"),
    ("plus_times", 32768, 1 << 30, 1, AT - 1, "dot", 32768, 32768, True,
     "scan"),
    ("plus_times", 32768, 1 << 30, 1, AT, "scatter", 32768, 32768, True,
     "windowed"),
    ("plus_times", 32768, 1 << 30, 1, AT - 1, "scatter", 32768, 32768, True,
     "scan"),
    # ... counted over the whole grid: a tile's cells times pr^2
    ("plus_times", 16384, 1 << 28, 2, AT, "scatter", 16384, 16384, True,
     "windowed"),
    ("plus_times", 16384, 1 << 28, 4, AT, "scatter", 16384, 16384, True,
     "scan"),
    # no multiplies at all count as one
    ("plus_times", LINE, LINE, 1, 0.0, "scatter", LINE, LINE, False,
     "windowed"),
    ("plus_times", LINE + 1, LINE + 1, 1, 0.0, "scatter", LINE + 1, LINE + 1,
     False, "scan"),
    # the clustering cell's neighbours of the line at n = 2^14: its ninth
    # iteration's multiplies (26 cells each) and its tenth's (293)
    ("plus_times", 16384, 1 << 28, 1, 10341856.0, "dot", 16384, 16384, True,
     "windowed"),
    ("plus_times", 16384, 1 << 28, 1, 914625.0, "dot", 16384, 16384, True,
     "scan"),
    # a dot panel: 2^18 padded rows under a 512-wide window, and past it
    ("plus_times", 262144, 1 << 30, 1, 2.0 ** 28, "dot", 262144, None, True,
     "windowed"),
    ("plus_times", 262145, 1 << 30, 1, 2.0 ** 28, "dot", 262145, None, True,
     "scan"),
    ("plus_times", 262145, 1 << 30, 1, 2.0 ** 28, "scatter", 262145, None,
     True, "windowed"),
    # ... k_dim left out is the largest tile dimension
    ("plus_times", 262145, 1 << 30, 1, 2.0 ** 28, "dot", None, None, True,
     "scan"),
    # ... and a window no narrower than n / 32
    ("plus_times", 262144, 1 << 32, 1, 2.0 ** 30, "dot", 16384, 262144, True,
     "windowed"),
    ("plus_times", 262145, 1 << 32, 1, 2.0 ** 30, "dot", 16384, 262145, True,
     "scan"),
    # a scatter combiner and no dense kernel: the scatter backend alone
    ("select2nd_max", 4096, 4096 * 4096, 1, 1e8, "dot", 4096, 4096, True,
     "scan"),
    ("select2nd_max", 4096, 4096 * 4096, 1, 1e8, "scatter", 4096, 4096, True,
     "windowed"),
    ("select2nd_max", 131072, (1 << 33) + 1, 1, 2.0 ** 30, "scatter", 16384,
     65536, True, "scan"),
    ("select2nd_max", 32768, 1 << 30, 1, AT - 1, "scatter", 32768, 32768,
     True, "scan"),
    # neither: the sorts
    ("generic", 4096, 4096 * 4096, 1, 1e8, "dot", 4096, 4096, True, "scan"),
    ("generic", 4096, 4096 * 4096, 1, 1e8, "scatter", 4096, 4096, True,
     "scan"),
    ("generic", 64, 64 * 64, 1, 1e4, "scatter", 64, 64, False, "scan"),
]


@pytest.mark.parametrize(
    "sr,dim,cells,pr,flops,backend,k_dim,n_dim,allow_mxu,tier", RULE,
    ids=[f"{r[0]}-{r[5]}-{i}" for i, r in enumerate(RULE)])
def test_the_rule(sr, dim, cells, pr, flops, backend, k_dim, n_dim,
                  allow_mxu, tier):
    assert S.choose_tier_from_counts(
        SRS[sr], dim, cells, pr, flops, backend, k_dim=k_dim,
        n_dim=n_dim, allow_mxu=allow_mxu) == tier


# --- both entries, one rule ---------------------------------------------------


def _coo(seed, m, k, nnz, *, repeats=0):
    """``nnz`` distinct entries of an [m, k] matrix of ones, the first
    ``repeats`` of them stored twice."""
    rng = np.random.default_rng(seed)
    flat = rng.choice(m * k, nnz, replace=False)
    r, c = flat // k, flat % k
    return (np.concatenate([r, r[:repeats]]),
            np.concatenate([c, c[:repeats]]))


def _mat(grid, rc, m, k):
    r, c = rc
    return SpParMat.from_global_coo(
        grid, r, c, np.ones(len(r), np.float32), m, k)


def _dense(M: SpParMat) -> np.ndarray:
    out = np.zeros((M.nrows, M.ncols), np.float64)
    r, c, v = M.to_global_coo()
    np.add.at(out, (np.asarray(r), np.asarray(c)), np.asarray(v))
    return out


#: name -> (grid, backend, A's and B's shapes and entries, the tier).
PAIRS = {
    "square-1x1": ((1, 1), "dot", (64, 64, 400, 0), None, "mxu"),
    "rectangular-1x1": (
        (1, 1), "scatter", (64, 48, 300, 0), (48, 80, 350, 0), "mxu"),
    "repeats-1x1-dot": ((1, 1), "dot", (96, 96, 900, 40), None, "windowed"),
    "repeats-1x1-scatter": (
        (1, 1), "scatter", (96, 96, 900, 40), None, "windowed"),
    "sparse-1x1": ((1, 1), "dot", (256, 256, 60, 5), None, "scan"),
    "square-2x2": ((2, 2), "scatter", (64, 64, 400, 0), None, "mxu"),
    "repeats-2x2": ((2, 2), "scatter", (96, 96, 900, 40), None, "windowed"),
    "sparse-rectangular-2x2": (
        (2, 2), "dot", (256, 128, 60, 5), (128, 256, 60, 5), "scan"),
}


@pytest.mark.parametrize("pair", sorted(PAIRS))
def test_auto_and_job_choose_one_tier_and_give_one_product(pair):
    shape, backend, a, b, tier = PAIRS[pair]
    grid = Grid.make(*shape)
    A = _mat(grid, _coo(1, *a[:3], repeats=a[3]), *a[:2])
    B = A if b is None else _mat(
        grid, _coo(2, *b[:3], repeats=b[3]), *b[:2])
    assert S.choose_spgemm_tier(PLUS_TIMES, A, B, backend=backend) == tier
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        C = S.spgemm_auto(PLUS_TIMES, A, B, backend=backend)
        assert obs.registry.get_counter(
            "spgemm.auto.tier", tier=tier, sr="plus_times") == 1
    finally:
        obs.disable()
        obs.reset()
    Cj, digest = S.spgemm_job(PLUS_TIMES, A, B, backend=backend)
    assert (digest["tier"], digest["backend"]) == (tier, backend)
    want = _dense(A) @ _dense(B)
    np.testing.assert_array_equal(_dense(C), want)
    np.testing.assert_array_equal(_dense(Cj), want)
    assert digest["sum"] == int(want.sum())


# --- a hostile environment ----------------------------------------------------


def _mats3d():
    g3 = Grid3D.make(2, 2, 2)
    rng = np.random.default_rng(3)
    n, m = 64, 500
    r, c = rng.integers(0, n, m), rng.integers(0, n, m)
    v = rng.integers(1, 4, m).astype(np.float32)
    return (SpParMat3D.from_global_coo(g3, r, c, v, n, n, split="col"),
            SpParMat3D.from_global_coo(g3, r, c, v, n, n, split="row"))


#: The operands every choice below is made on: a one-tile matrix with
#: repeated entries (no whole-tile rung: the rule reads the counts),
#: the same on a 2x2 grid, and a pair on a layered mesh.
@pytest.fixture(scope="module")
def operands():
    rc = _coo(4, 96, 96, 300, repeats=30)
    return (_mat(Grid.make(1, 1), rc, 96, 96),
            _mat(Grid.make(2, 2), rc, 96, 96)) + _mats3d()


#: The series a route shows in: any ``tuner.*`` or ``spgemm.auto.*``
#: counter, and the windowed tier's and the merge's own.
def _routed(name):
    return name.startswith(("tuner.", "spgemm.auto.")) or name in (
        "spgemm.windowed.dispatch", "spgemm.windowed.windows_packed",
        "spgemm.merge.tier")


def _choices(operands, monkeypatch):
    """Everything a name could re-route, from one run: the tier by the
    rule, both backends, the windowed plan ``spgemm_auto`` runs under
    (geometry and capacities), the dispatch of a product on a grid,
    ``spgemm3d``'s tier and merge, and every routing series counted."""
    A1, A4, A3, B3 = operands
    plans = []
    plan_windowed = S.plan_windowed

    def spy(*a, **kw):
        plans.append(plan_windowed(*a, **kw))
        return plans[-1]

    monkeypatch.setattr(S, "plan_windowed", spy)
    out = {
        "tier": S.choose_spgemm_tier(PLUS_TIMES, A1, A1, backend="dot"),
        "backend": S.resolve_spgemm_backend(),
        "spmm": [resolve_spmm_backend(sr) for sr in (PLUS_TIMES, MIN_PLUS)],
    }
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        S.spgemm_auto(PLUS_TIMES, A1, A1, backend="dot")
        S.spgemm_auto(PLUS_TIMES, A4, A4, block_rows=16)
        spgemm3d(PLUS_TIMES, A3, B3)
        out["series"] = sorted(
            (r["name"], tuple(sorted(r["labels"].items())), r["value"])
            for r in obs.registry.snapshot()
            if r["kind"] == "counter" and _routed(r["name"]))
    finally:
        obs.disable()
        obs.reset()
    out["plans"] = [
        (p.backend, p.block_rows, p.block_cols, p.flop_caps, p.out_caps,
         p.skip, p.panel_cap) for p in plans]
    return out


#: What a product is routed by with none of the names set.
UNSET = {
    "tier": "windowed",
    "backend": "scatter",
    "spmm": ["mxu_gather", "scatter"],
    "series": [
        # repeated entries: the rule is read without its first rung
        ("spgemm.auto.dedup_fallback", (("sr", "plus_times"),), 2),
        ("spgemm.auto.tier",
         (("sr", "plus_times"), ("tier", "windowed")), 2),
        ("spgemm.merge.tier",
         (("op", "spgemm3d"), ("source", "heuristic"), ("tier", "sort")), 1),
        ("spgemm.windowed.dispatch", (("mode", "blocked"),), 1),
        ("spgemm.windowed.dispatch", (("mode", "local"),), 1),
        ("spgemm.windowed.windows_packed", (), 4),
    ],
}


def _plan_store(tmp_path, operands):
    """A ``plans.jsonl`` in the schema of PR 42's ``tuner/store.py``
    holding, under the keys of the products ``_choices`` runs, records
    that say otherwise: the sort for the products on a grid, the
    windowed tier and a hash merge for the layered one."""
    A1, A4, A3, _ = operands

    def lines(op, M, grid, grid3, plan):
        nnz = int(np.asarray(jax.device_get(M.getnnz())))
        bucket = (int(M.nrows) - 1).bit_length()
        band = round(math.log2(nnz / M.nrows))
        for backend in ("dot", "scatter", ""):
            for b in (band - 1, band, band + 1):
                yield json.dumps({"v": "combblas_tpu.plans/v1", "key": {
                    "op": op, "shape": [bucket] * 3, "band": [b, b],
                    "sr": "plus_times", "backend": backend, "grid": grid,
                    "grid3": grid3, "platform": "cpu"}, "plan": plan})

    plan = {"tier": "esc", "block_rows": 8, "block_cols": 32,
            "dispatch": "fused", "merge": "runs", "source": "manual"}
    text = "\n".join(
        list(lines("spgemm", A1, "1x1", "", plan))
        + list(lines("spgemm", A4, "2x2", "", plan))
        + list(lines("spgemm3d", A3, "2x2", "2x2x2",
                     {"tier": "windowed", "merge": "hash",
                      "source": "manual"})))
    (tmp_path / "plans.jsonl").write_text(text + "\n")
    return str(tmp_path)


#: name -> a value that sent a product elsewhere until PR 43.
HOSTILE = {
    "COMBBLAS_SPGEMM_TIER": "esc",
    "COMBBLAS_SPGEMM_BACKEND": "dot",
    "COMBBLAS_SPGEMM_BLOCK_ROWS": "8",
    "COMBBLAS_SPGEMM_BLOCK_COLS": "32",
    "COMBBLAS_SPGEMM_DISPATCH": "fused",
    "COMBBLAS_SPGEMM_BUCKET_CAPS": "0",
    "COMBBLAS_SPGEMM_MERGE": "hash",
    "COMBBLAS_SPGEMM3D_TIER": "windowed",
    "COMBBLAS_SPMM_BACKEND": "scatter",
    "COMBBLAS_TUNER_PROBE": "1",
    "COMBBLAS_PLAN_STORE": _plan_store,
}


@pytest.fixture(scope="module")
def unset(operands):
    with pytest.MonkeyPatch.context() as mp:
        for name in HOSTILE:
            mp.delenv(name, raising=False)
        return _choices(operands, mp)


def test_with_no_name_set_a_product_is_routed_by_its_counts(unset):
    assert {k: unset[k] for k in UNSET} == UNSET
    one_tile, on_a_grid = unset["plans"]
    # the library's own geometry; every capacity a power of two, or the
    # cells of its window
    assert one_tile[:3] == ("dot", 96, 96)
    assert on_a_grid[:3] == ("scatter", 16, None)
    caps = [c for row in one_tile[3] + one_tile[4] for c in row]
    caps += list(on_a_grid[3]) + list(on_a_grid[4])
    assert all(c & (c - 1) == 0 or c in (96 * 96, 16 * 48) for c in caps)
    assert any(c not in (1, 96 * 96, 16 * 48) for c in caps)


@pytest.mark.parametrize("name", sorted(HOSTILE))
def test_no_environment_name_routes_a_product(
        name, unset, operands, tmp_path, monkeypatch):
    value = HOSTILE[name]
    monkeypatch.setenv(
        name, value(tmp_path, operands) if callable(value) else value)
    assert _choices(operands, monkeypatch) == unset


# --- the other two routers, as tables -----------------------------------------


@pytest.mark.parametrize("sr,backend,want", [
    (PLUS_TIMES, None, "mxu_gather"),
    (PLUS_TIMES, "mxu_gather", "mxu_gather"),
    (PLUS_TIMES, "scatter", "scatter"),
    (MIN_PLUS, None, "scatter"),
    (MAX_MIN, None, "scatter"),
    (SELECT2ND_MAX, None, "scatter"),
    (MIN_PLUS, "scatter", "scatter"),
    (MIN_PLUS, "mxu_gather", ValueError),
    (PLUS_TIMES, "mxu", ValueError),
], ids=lambda v: getattr(v, "name", None) or getattr(v, "__name__", str(v)))
def test_spmm_backend(sr, backend, want):
    if want is ValueError:
        with pytest.raises(ValueError, match=repr(backend)):
            resolve_spmm_backend(sr, backend)
    else:
        assert resolve_spmm_backend(sr, backend) == want


@pytest.mark.parametrize("tier,merge,ran,merged,source", [
    (None, None, "esc", "sort", "heuristic"),
    (None, "runs", "esc", "runs", "arg"),
    ("esc", "hash", "esc", "hash", "arg"),
    ("windowed", None, "windowed", "runs", "heuristic"),
    ("windowed", "sort", "windowed", "sort", "arg"),
])
def test_spgemm3d_tier_and_merge(tier, merge, ran, merged, source):
    """``tier`` None is ``esc`` and ``merge`` None the entry's own
    heuristic (two layers of unsorted chunks: one sort; the windowed
    tier's row blocks arrive sorted: runs)."""
    A3, B3 = _mats3d()
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        spgemm3d(PLUS_TIMES, A3, B3, tier=tier, merge=merge)
        assert obs.registry.get_counter(
            "spgemm.merge.tier", tier=merged, source=source,
            op="spgemm3d") == 1
        # the windowed tier alone packs windows
        packed = obs.registry.get_counter("spgemm.windowed.windows_packed")
        assert bool(packed) == (ran == "windowed")
    finally:
        obs.disable()
        obs.reset()


@pytest.mark.parametrize("kw", [
    dict(tier="mxu"), dict(merge="hashed"),
    dict(tier="windowed", merge="hashed"),
], ids=str)
def test_spgemm3d_refuses_an_argument_it_does_not_know(kw):
    A3, B3 = _mats3d()
    with pytest.raises(AssertionError, match=list(kw.values())[-1]):
        spgemm3d(PLUS_TIMES, A3, B3, **kw)
    assert mesh3d.MERGE_TIERS == ("sort", "runs", "hash")
