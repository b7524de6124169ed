"""One whole clustering as one job (``models/mcl.py:mcl_job``): against
the benchmark's float64 reference (``chipbench/mclref.py``) at scale
8-10 of the cell's generator, partition, iteration count, chaos and the
states after iterations held to the configuration's limits; recovery
fired; one job under a dense tier AND under ``scan``; the select on a
dense window against ``mcl_prune_recovery_select`` entry for entry and
against a plain sort; nothing compiled by a second job; no argument
that names a loop, a tier, a phase count or a backend.

The published select 1100 / recover 1400 cannot bind under n = 1,100:
the cases here scale both with the graph so that they do."""

import inspect
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import famgraph, mclref
from chipbench.spec import CHECKOUT
from combblas_tpu import obs
from combblas_tpu.models import mcl as M
from combblas_tpu.ops import spgemm as O
from combblas_tpu.parallel import spgemm as S
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat

#: a small graph of the cell's generator, and a select / recover that
#: bind on it
SMALL = dict(degree=24, smax=96)
KW = dict(select=40, recover=60)


def _limits():
    with open(os.path.join(
            CHECKOUT, "chipbench", "configs", "hipmcl-fam-1x1.json")) as f:
        return json.load(f)["limits"]


def _graph(scale, seed=1, **params):
    n, rows, cols, vals, _ = famgraph.family_graph(
        scale, seed, **dict(SMALL, **params))
    return n, rows, cols, vals


def _upload(n, rows, cols, vals):
    return SpParMat.from_global_coo(Grid.make(1, 1), rows, cols, vals, n, n)


def _labels(labels, n):
    return np.asarray(labels.blocks).reshape(-1)[:n]


@pytest.fixture(scope="module")
def s9():
    n, rows, cols, vals = _graph(9)
    ref = mclref.mcl_reference(n, rows, cols, vals, keep=range(1, 16), **KW)
    return n, _upload(n, rows, cols, vals), ref


@pytest.fixture
def small_dense_envelope(monkeypatch):
    """At a test's size every product is inside the whole-tile ``mxu``
    rung; with the rung cut to 128 the rule reads the multiply count, as
    it does at the cell's n = 16,384.  A converged job's product is n
    multiplies, n cells each, so under the chip's line a job of a few
    hundred vertices leaves the dense tier late or never: at 16 cells a
    multiply it crosses mid-job, as the cell's does."""
    monkeypatch.setattr(S, "MXU_MAX_TILE_DIM", 128)
    monkeypatch.setattr(S, "WINDOWED_MAX_CELLS_PER_FLOP", 16.0)


class _Compiles:
    """Programs compiled, by JAX's own monitoring event."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _crossing(tiers) -> int:
    """How many iterations ran dense: the rule leaves the dense tier
    once and for good."""
    k = sum(t == "windowed" for t in tiers)
    assert tiers == ("windowed",) * k + ("scan",) * (len(tiers) - k)
    return k


def _held(n, ref, labels, digest, states=None):
    """The cell's checks (b) and (c) on one job, every column."""
    assert mclref.check_digest(ref, digest, _limits()) is None
    assert np.array_equal(_labels(labels, n), ref["labels"])
    for it, got in (states or {}).items():
        bad, _, _ = mclref.check_matrix(
            n, got, ref["matrices"][it], np.arange(n), _limits(),
            f"iteration {it}")
        assert bad is None, bad


@pytest.mark.parametrize("scale,seed,kw", [
    (8, 1, dict(select=24, recover=36)), (8, 2, dict(select=24, recover=36)),
    (9, 1, KW), (9, 3, KW), (10, 1, dict(select=48, recover=64)),
])
def test_a_job_gives_the_reference_s_clustering(scale, seed, kw):
    n, rows, cols, vals = _graph(scale, seed)
    ref = mclref.mcl_reference(n, rows, cols, vals, keep=(1, 2, 3), **kw)
    assert ref["counts"][0]["bound"] > 0  # the select binds
    states = {}

    def hook(it, tier, fetch):
        if it in (1, 2, 3):
            states[it] = fetch()

    labels, digest = M.mcl_job(_upload(n, rows, cols, vals), hook=hook, **kw)
    assert sorted(states) == [1, 2, 3]
    _held(n, ref, labels, digest, states)
    assert digest["iters"] == ref["iters"] == len(digest["chaos"])
    assert digest["chaos"].dtype == np.float32
    assert digest["clusters"] == len(np.unique(ref["labels"]))
    assert digest["fingerprint"] == mclref.fingerprint(ref["labels"])
    # a label is the smallest vertex of its cluster
    lab = _labels(labels, n)
    assert np.array_equal(lab[lab], lab) and np.all(lab <= np.arange(n))


def test_recovery_fires_and_the_job_counts_it(s9):
    n, A, ref = s9
    assert ref["counts"][0]["recovered"] > 0
    obs.reset()
    obs.enable(install_hooks=False)
    try:
        labels, digest = M.mcl_job(A, **KW)
        spans = [s for s in obs.spans() if s["name"] == "mcl.iter"]
        job = [s for s in obs.spans() if s["name"] == "mcl.job"]
        counters = {
            (r["name"], tuple(sorted(r["labels"].items()))): r["value"]
            for r in obs.registry.snapshot() if r["kind"] == "counter"}
        tables = obs.opnames.tables()
    finally:
        obs.disable()
        obs.reset()
    _held(n, ref, labels, digest)
    counts = ref["counts"][:digest["iters"]]
    assert counters[("mcl.job.jobs", ())] == 1
    assert counters[("mcl.job.recovered_cols", ())] == sum(
        c["recovered"] for c in counts)
    assert counters[("mcl.job.select_bound_cols", ())] == sum(
        c["bound"] for c in counts)
    assert counters[("mcl.job.stored", ())] == sum(digest["stored"])
    # the select's candidates are a fraction of the unpruned product,
    # and what is stored a fraction of them: nothing unpruned was tuples
    cand = counters[("mcl.job.candidates", ())]
    assert sum(digest["stored"]) <= cand <= 1.001 * sum(
        c["candidates"] for c in counts)
    assert cand < sum(c["unpruned"] for c in counts)
    assert counters[("mcl.job.products", ())] == pytest.approx(
        sum(c["products"] for c in counts), rel=1e-3)
    # one span an iteration, carrying what the digest carries
    assert len(job) == 1 and job[0]["attrs"]["iters"] == digest["iters"]
    assert [s["attrs"]["tier"] for s in spans] == list(digest["tiers"])
    assert [s["attrs"]["stored"] for s in spans] == list(digest["stored"])
    assert {s["path"] for s in spans} == {"mcl.job/mcl.iter"}
    assert counters[("mcl.job.iters", (("tier", "mxu"),))] == digest["iters"]
    # the first traced job published its programs' scopes
    found = {c for t in tables.values() for op in t.values()
             for c in op.split("/") if c.startswith("mcl.")}
    assert found == set(M.MCL_SCOPES)


def test_one_job_runs_a_dense_and_a_scan_iteration(
        s9, small_dense_envelope, monkeypatch):
    n, A, ref = s9
    states, sized = {}, []

    def hook(it, tier, fetch):
        states[it] = (tier, fetch())

    expand = M._mcl_scan_expand
    monkeypatch.setattr(
        M, "_mcl_scan_expand",
        lambda S_, **kw: (sized.append(kw), expand(S_, **kw))[1])
    labels, digest = M.mcl_job(A, hook=hook, **KW)
    tiers = digest["tiers"]
    assert set(tiers) == {"windowed", "scan"}
    # the rule leaves the dense tier once and for good, at the line
    # (``WINDOWED_MAX_CELLS_PER_FLOP``)
    k = _crossing(tiers)
    line = n * n / S.WINDOWED_MAX_CELLS_PER_FLOP
    assert ref["counts"][k - 1]["products"] >= line > ref["counts"][k][
        "products"]
    _held(n, ref, labels, digest,
          {it: got for it, (_, got) in states.items()})
    assert [t for t, _ in states.values()] == list(tiers)
    # a sparse iteration is sized by ITS expansion, the first one after
    # the dense state too (the dense iteration before it counted the
    # slots): a walk is a chunk at least and under a chunk more than
    # its multiplies.  At the cell's size the job's first expansion's
    # slots are 28 GB of sort here
    assert len(sized) == len(tiers) - k
    for kw, c, operand in zip(sized, ref["counts"][k:],
                              digest["stored"][k - 1:]):
        assert kw["flop_cap"] <= 2 * 1.05 * (
            c["products"] + O.CHUNK_W * int(operand)) + 2
    # and the same job under the whole-tile rung is the same clustering
    # (the state after iteration k is the same matrix, whichever tier
    # hands it over)


@pytest.mark.parametrize("line", [1.0, 16.0, None, 4096.0], ids=[
    "1", "16", "shipped", "4096"])
def test_the_line_moves_the_crossing_and_not_the_clustering(
        s9, monkeypatch, line):
    """Wherever the line stands (``None``: where the library has it), a
    job is the reference's clustering; the line decides only how many
    iterations run dense."""
    n, A, ref = s9
    monkeypatch.setattr(S, "MXU_MAX_TILE_DIM", 128)
    if line is not None:
        monkeypatch.setattr(S, "WINDOWED_MAX_CELLS_PER_FLOP", line)
    labels, digest = M.mcl_job(A, **KW)
    tiers = digest["tiers"]
    k = _crossing(tiers)
    counts = [c["products"] for c in ref["counts"][:len(tiers)]]
    at = n * n / S.WINDOWED_MAX_CELLS_PER_FLOP
    assert k == sum(p >= at for p in counts)
    assert digest["iters"] == ref["iters"]
    assert digest["clusters"] == len(np.unique(ref["labels"]))
    assert digest["fingerprint"] == mclref.fingerprint(ref["labels"])
    np.testing.assert_allclose(
        digest["stored"], ref["stored"][:len(tiers)],
        rtol=_limits()["stored_rel"])


def test_the_tiers_agree_on_every_iteration(s9, monkeypatch):
    n, A, ref = s9
    whole = M.mcl_job(A, **KW)[1]
    monkeypatch.setattr(S, "MXU_MAX_TILE_DIM", 128)
    # several row blocks a dense iteration
    monkeypatch.setattr(S, "WINDOWED_BLOCK_CELLS", 1 << 16)
    mixed = M.mcl_job(A, **KW)[1]
    assert S.default_block_rows(512, 512) == 128
    assert set(whole["tiers"]) == {"mxu"} != set(mixed["tiers"])
    assert whole["iters"] == mixed["iters"]
    assert whole["fingerprint"] == mixed["fingerprint"]
    assert np.array_equal(whole["stored"], mixed["stored"])
    np.testing.assert_allclose(whole["chaos"], mixed["chaos"], rtol=1e-4,
                               atol=1e-6)


def test_a_second_job_compiles_nothing(small_dense_envelope):
    n, rows, cols, vals = _graph(8, seed=5)
    A = _upload(n, rows, cols, vals)
    kw = dict(select=24, recover=36)
    watch = _Compiles()
    first = M.mcl_job(A, **kw)[1]
    assert watch.count > 0 and "scan" in first["tiers"]
    before = watch.count
    again = M.mcl_job(A, **kw)[1]
    assert watch.count == before
    assert again["fingerprint"] == first["fingerprint"]
    assert np.array_equal(again["chaos"], first["chaos"])


def test_the_dense_select_is_mcl_prune_recovery_select_entry_for_entry(rng):
    """One expanded matrix, both ways: the sparse loop's select on
    tuples (two ``kselect`` thresholds) and the job's on the dense
    transposed window."""
    n = 96
    c = rng.random((n, n)).astype(np.float32) ** 6  # a long tail
    c[rng.random((n, n)) < 0.3] = 0
    c[:, 5] = 0  # an empty column
    c[:7, 9] = 0.25  # ties at a threshold
    c /= c.sum(axis=0, keepdims=True) + 1e-30
    kw = dict(hard_threshold=1e-3, select_num=20, recover_num=30,
              recover_pct=0.9)
    want = M.mcl_prune_recovery_select(
        SpParMat.from_dense(Grid.make(1, 1), c), **kw).to_dense()
    got, counts = jax.jit(
        lambda x: O.mcl_select_rows(x, 1e-3, 20, 30, 0.9))(jnp.asarray(c.T))
    assert np.array_equal(np.asarray(got).T, want)
    cand = (c >= 1e-3).sum(axis=0)
    assert counts.tolist()[:2] == [int(cand.sum()), int((cand > 20).sum())]
    assert 0 < counts[2] <= counts[1]
    assert np.count_nonzero(want) < cand.sum()
    # nothing to cut: every column under the select
    same, zero = O.mcl_select_rows(jnp.asarray(c.T), 1e-3, n, n, 0.9)
    assert np.array_equal(np.asarray(same), np.where(c.T < 1e-3, 0, c.T))
    assert zero.tolist()[1:] == [0, 0]


@pytest.mark.parametrize("ks", [(1, 2), (5, 17), (64, 64)])
def test_a_row_s_kth_largest_is_the_sorted_row_s(rng, ks):
    x = rng.random((33, 64)).astype(np.float32)
    x[rng.random(x.shape) < 0.5] = 0
    x[3] = 0
    x[4, :20] = 0.5
    got = O.rows_kth_largest(jnp.asarray(x), ks)
    desc = -np.sort(-x, axis=1)
    for g, k in zip(got, ks):
        assert np.array_equal(np.asarray(g), desc[:, k - 1])


def test_a_job_names_no_loop_tier_phase_count_or_backend():
    params = inspect.signature(M.mcl_job).parameters
    assert list(params) == [
        "A", "inflation", "select", "recover", "recover_pct", "prune",
        "eps", "max_iters", "mode", "hook"]
    assert all(p.kind is p.KEYWORD_ONLY for n, p in params.items()
               if n != "A")
    # the published defaults (MCL.cpp InitParam)
    assert [params[k].default for k in (
        "inflation", "select", "recover", "recover_pct", "prune")] == [
        2.0, 1100, 1400, 0.9, 1e-4]
    # nothing inside the job reads the environment or a file
    src = inspect.getsource(M.mcl_job)
    assert "os.environ" not in src and "getenv" not in src
    assert "open(" not in src
    assert "JOB_BACKEND" in src and "choose_tier_from_counts" in src


def test_the_rule_for_the_chip_crosses_the_ladder_at_the_cell_s_size():
    """The reference's multiply counts at the shipped scale, through
    the rule alone: dense tiers first, ``scan`` after."""
    from combblas_tpu.semiring import PLUS_TIMES

    with open(os.path.join(
            CHECKOUT, "chipbench", "configs", "hipmcl-fam-1x1.json")) as f:
        cfg = json.load(f)
    n = 1 << cfg["scale"]
    products = cfg["assumed"]["counts"]["products"]
    tiers = [S.choose_tier_from_counts(
        PLUS_TIMES, n, n * n, 1, p, S.JOB_BACKEND, k_dim=n, n_dim=n)
        for p in products]
    # nine dense products, then nine sorts (PR 46: the line re-derived
    # on the chip), and no count within a tenth of the line, where a
    # rounding of the program's count against the reference's could
    # flip a tier
    assert len(products) == 18 and _crossing(tuple(tiers)) == 9
    line = n * n / S.WINDOWED_MAX_CELLS_PER_FLOP
    assert all(abs(p / line - 1) > 0.1 for p in products)


def test_the_split_product_s_halves_add_up_to_the_operand(rng):
    """``bf16x3``'s hi half is ``lax.reduce_precision`` (a cast and its
    way back is a round trip the chip's compiler keeps in float32, which
    left the lo half 0 there): hi is the operand rounded to bfloat16,
    hi + lo lies within 2^-16 of the operand, signs, zeros and the
    smallest normals included, and the product carries it."""
    x = (rng.random((64, 256)) * 4 - 2).astype(np.float32)
    x[0, :4] = [0.0, -0.0, 1.17549435e-38, -3.0e38]
    hi, lo = S._split_bf16(jnp.asarray(x))
    assert hi.dtype == lo.dtype == jnp.bfloat16
    assert np.array_equal(
        np.asarray(hi.astype(jnp.float32)),
        np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)))
    got = np.asarray(hi.astype(jnp.float32), np.float64) + np.asarray(
        lo.astype(jnp.float32), np.float64)
    assert np.all(np.abs(got - x) <= 2.0 ** -16 * np.abs(x))
    y = rng.random((256, 64)).astype(np.float32)
    want = x.astype(np.float64) @ y.astype(np.float64)
    err = {mode: np.abs(np.asarray(S._mxu_dot(
        jnp.asarray(x), jnp.asarray(y), mode, jnp.float32)) - want).max()
        for mode in ("bf16", "bf16x3")}
    assert err["bf16x3"] < err["bf16"] / 64
