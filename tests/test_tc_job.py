"""``models/tc.py:tc_job``, GAP's TC trial as the analysts' path runs it:
the triple against every kernel of ``triangle_count`` and the definition
on small R-MAT graphs with repeated edges and loops in the input, the
two counts against the host's, the scopes in the lowered text and out of
the instructions, the counters once a job, the same triple with
telemetry off, and ``ops/spgemm.py``'s other programs untouched."""

import contextlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chipbench import graph, tcref  # noqa: E402
from combblas_tpu import obs  # noqa: E402
from combblas_tpu.models import tc  # noqa: E402
from combblas_tpu.ops import spgemm as ops  # noqa: E402
from combblas_tpu.parallel.grid import Grid  # noqa: E402
from combblas_tpu.parallel.spmat import SpParMat  # noqa: E402

KERNELS = ("auto", "dense", "edgeharvest", "sparse")
CHUNK = 8192  # the harvest's pair chunk


def _noisy(scale, seed=1):
    """The scale's R-MAT graph as ``graph.rmat_graph`` makes it, and the
    same edge list with loops and repeated entries put back in."""
    n, rows, cols, _ = graph.rmat_graph(scale, 16, seed)
    rng = np.random.default_rng(seed)
    again = rng.choice(len(rows), len(rows) // 7, replace=False)
    loops = rng.choice(n, 9, replace=False).astype(np.int32)
    r = np.concatenate([rows, rows[again], loops])
    c = np.concatenate([cols, cols[again], loops])
    order = rng.permutation(len(r))
    return n, rows, cols, r[order], c[order]


def _mat(n, rows, cols):
    return SpParMat.from_global_coo(
        Grid.make(1, 1), rows, cols, np.ones(len(rows), np.float32), n, n)


@pytest.fixture(scope="module", params=[7, 8, 9])
def case(request):
    n, rows, cols, r, c = _noisy(request.param)
    return n, rows, cols, _mat(n, r, c), len(r)


def test_job_counts_what_the_definition_counts(case):
    n, rows, cols, A, stored = case
    triangles, pairs, edges = tc.tc_job(A)
    assert all(type(v) is int for v in (triangles, pairs, edges))
    assert triangles == tcref.brute_force(n, rows, cols) > 0
    assert triangles == tcref.TCReference(n, rows, cols).triangles
    # the two counts are the host's: the undirected edges once the loops
    # and repeats are gone, and the same chunk-padded: the scan walks the
    # kept pairs alone, not the stored slots
    assert edges == len(rows) // 2 == len(tcref.undirected_edges(
        n, rows, cols)[0])
    assert pairs == -(-edges // CHUNK) * CHUNK
    assert stored > 2 * edges


def _path_plus(n, m):
    """``m`` undirected edges on ``n`` vertices, triangles among them:
    the path 0-1-...-(n-1), then the chords (i, i+2), (i, i+3), ... in
    turn; one direction an edge, as (larger, smaller)."""
    out = [(i + step, i) for step in range(1, n) for i in range(n - step)]
    assert len(out) >= m
    e = np.array(out[:m], np.int32)
    return e[:, 0], e[:, 1]


def _stored(case, c):
    """``(n, rows, cols)`` as stored, and the clean symmetric list the
    reference takes."""
    rng = np.random.default_rng(c)
    if case == "loops-and-repeats-only":
        loops = np.arange(0, 60, 3, dtype=np.int32)
        r = np.concatenate([loops, loops, loops[:7]])
        none = np.zeros(0, np.int32)
        return 64, r, r, none, none
    if case == "upper-first-shuffled":
        n, rows, cols, _ = graph.rmat_graph(8, 16, 3)
        up = rows < cols
        order = np.concatenate([
            rng.permutation(np.flatnonzero(up)),
            rng.permutation(np.flatnonzero(~up))])
        return n, rows[order], cols[order], rows, cols
    m = {"exact-multiple": 3 * c, "one-past-a-chunk": c + 1}[case]
    hi, lo = _path_plus(40, m)
    rows, cols = np.concatenate([hi, lo]), np.concatenate([lo, hi])
    # loops and repeats in the stored list, and a shuffle over all of it
    again = rng.choice(len(rows), len(rows) // 5, replace=False)
    loops = np.arange(5, dtype=np.int32)
    r = np.concatenate([rows, rows[again], loops])
    k = np.concatenate([cols, cols[again], loops])
    order = rng.permutation(len(r))
    return 40, r[order], k[order], rows, cols


@pytest.mark.parametrize("case,c", [
    ("exact-multiple", 64), ("one-past-a-chunk", 64),
    ("loops-and-repeats-only", 64), ("upper-first-shuffled", 128)])
def test_the_scan_runs_the_steps_that_hold_a_kept_pair(case, c):
    """``_tc_edge_harvest_bits`` under a small ``chunk``, so the loop
    runs many steps here: the count is the definition's, ``edges`` the
    kept pairs, ``pairs`` those chunk-padded (a whole number of chunks
    adds no step, one pair more adds one, nothing kept runs none)."""
    n, r, k, rows, cols = _stored(case, c)
    hilo, pairs, edges = jax.jit(
        tc._tc_edge_harvest_bits, static_argnames=("n", "chunk"))(
            jnp.asarray(r), jnp.asarray(k), n=n, chunk=c)
    kept, want = len(rows) // 2, tcref.brute_force(n, rows, cols)
    got = (ops.combine_hilo(hilo), int(pairs), int(edges))
    assert got == (3 * want, -(-kept // c) * c, kept)
    if case == "loops-and-repeats-only":
        assert got == (0, 0, 0)
    else:
        assert want > 0 and pairs // c > 1
        assert (kept % c == 0) == (case == "exact-multiple")


@pytest.mark.parametrize("kernel", KERNELS)
def test_job_equals_every_kernel_of_triangle_count(case, kernel):
    n, rows, cols, A, _ = case
    # ``sparse`` expects a deduplicated edge list (its docstring): it
    # gets the clean one; the others mask repeats and loops themselves
    M = _mat(n, rows, cols) if kernel == "sparse" else A
    assert tc.triangle_count(M, kernel=kernel) == tc.tc_job(A)[0]


def test_job_refuses_what_the_table_cannot_hold():
    n = tc.EDGE_HARVEST_BITS_MAX_DIM * 2
    big = _mat(n, np.array([1, 0], np.int32), np.array([0, 1], np.int32))
    with pytest.raises(ValueError, match="n <= 262144"):
        tc.tc_job(big)
    with pytest.raises(ValueError, match="n <= 262144"):
        tc.triangle_count(big, kernel="edgeharvest")
    n, rows, cols, _ = graph.rmat_graph(7, 16, 1)
    mesh = SpParMat.from_global_coo(
        Grid.make(2, 2), rows, cols, np.ones(len(rows), np.float32), n, n)
    with pytest.raises(ValueError, match="one device"):
        tc.tc_job(mesh)


# --- scopes -----------------------------------------------------------------


def _strip(text: str) -> str:
    """Lowered text without locations (where the scopes live)."""
    text = re.sub(r" loc\([^\n]*\)$", "", text, flags=re.M)
    return "\n".join(ln for ln in text.splitlines()
                     if not ln.lstrip().startswith("#loc"))


def _lowered(fn, *args, **kw):
    return fn.lower(*args, **kw).as_text(debug_info=True)


def test_scopes_are_in_the_lowered_text_and_change_no_instruction(
        case, monkeypatch):
    n, _, _, A, _ = case
    args = (A.rows, A.cols)
    with_scopes = _lowered(tc.tc_edgeharvest_bits, *args, n=n)
    assert [s for s in tc.TC_SCOPES if s not in with_scopes] == []
    for path in ("tc.harvest/", "gather/", "popcount/"):
        assert path in with_scopes
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        without = _lowered(tc.tc_edgeharvest_bits, *args, n=n)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not any(s in without for s in tc.TC_SCOPES[:3])
    assert _strip(with_scopes) == _strip(without)
    # the program's name is its own: what a device trace and the
    # persistent compile cache know it by
    assert "module @jit_tc_edgeharvest_bits" in with_scopes


def test_other_programs_of_the_bit_oracle_are_what_they_were(rng):
    """``ops/spgemm.py`` gained two ``named_scope``s inside
    ``popcount_pair_counts`` and nothing else: ``spgemm_support_bits``
    (which MCL's dense path and ``spgemm_auto`` size their output with)
    calls none of the three scoped functions, so its text holds no scope
    of this PR, and the scoped step lowers to the instructions it
    lowered to before."""
    from combblas_tpu.ops.tuples import SpTuples

    def tuples(m, k):
        d = (rng.random((m, k)) < 0.1)
        r, c = np.nonzero(d)
        return SpTuples(
            rows=jnp.asarray(r, jnp.int32), cols=jnp.asarray(c, jnp.int32),
            vals=jnp.ones(len(r), jnp.float32),
            nnz=jnp.int32(len(r)), nrows=m, ncols=k)

    a, b = tuples(64, 48), tuples(48, 80)
    text = _lowered(jax.jit(
        lambda a, b: ops.spgemm_support_bits(a, b, row_block=64)), a, b)
    assert not any(s in text for s in tc.TC_SCOPES[:3] + (
        "popcount/", "gather/"))
    # the oracle's own pair count still equals the dense product's
    bits_a = ops.pack_support_bits(a.rows, a.cols, 64, 48)
    ii = jnp.zeros(CHUNK, jnp.int32).at[:64].set(jnp.arange(64))
    w = jnp.zeros(CHUNK, jnp.int32).at[:64].set(1)
    got = ops.combine_hilo(ops.popcount_pair_counts(
        bits_a, bits_a, ii, ii, w))
    assert got == int(np.asarray(a.vals).sum())  # |row i & row i| = nnz


# --- counters ---------------------------------------------------------------


def _counters():
    return {r["name"]: r["value"] for r in obs.registry.snapshot()
            if r["name"].startswith("models.tc.")}


def test_counters_add_once_a_job_and_telemetry_off_returns_the_same(case):
    _, _, _, A, _ = case
    obs.reset()
    off = tc.tc_job(A)
    assert _counters() == {} and obs.opnames.tables() == {}
    obs.enable(install_hooks=False)
    try:
        on = [tc.tc_job(A) for _ in range(3)]
        counted = _counters()
        tables = obs.opnames.tables()
    finally:
        obs.disable()
        obs.reset()
    assert on == [off] * 3
    triangles, pairs, edges = off
    assert counted == {
        "models.tc.jobs": 3, "models.tc.pairs": 3 * pairs,
        "models.tc.edges": 3 * edges, "models.tc.triangles": 3 * triangles,
        # the steps of the scan, under the loop that ran them (a CPU
        # runs the jnp loop: tests/test_tc_fused_harvest.py)
        "models.tc.harvest_steps": 3 * pairs // CHUNK,
        # and the pack that wrote the table (a CPU scatters:
        # tests/test_tc_pack_rows.py)
        "models.tc.pack": 3}
    # the first traced job published the program's op names, once
    names = set(tables["jit_tc_edgeharvest_bits"].values())
    assert any("/tc.harvest/" in nm and nm.endswith("/gather/gather")
               for nm in names)
    assert any("/tc.pack/" in nm for nm in names)
