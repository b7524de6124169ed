"""A FastSV round whose grandparents did not change sweeps nothing
(``models/cc.py:_fastsv``): the program's labels, rounds and jumps are
those of a plain numpy FastSV that sweeps EVERY round, entry for entry,
and its ``sweeps`` (the jitted programs' fourth output) is the count of
that reference's rounds whose ``gf = f[f]`` is not the round before's,
over both matrix types, one tile and a 2 x 2 mesh, cold and warm
starts."""

import numpy as np
import pytest

from combblas_tpu.models import cc
from combblas_tpu.parallel.ellmat import EllParMat
from combblas_tpu.parallel.grid import Grid
from combblas_tpu.parallel.spmat import SpParMat
from combblas_tpu.parallel.vec import DistVec

_BIG = np.iinfo(np.int32).max


def reference(n, rows, cols, f0=None):
    """FastSV as ``_fastsv`` states it, one sweep a round whatever the
    round reads: ``(labels, rounds, jumps, fresh)``, ``fresh`` the rounds
    whose ``gf`` differs from the round before's (the first included)."""
    f = np.arange(n, dtype=np.int64) if f0 is None else f0.astype(np.int64)
    rounds = fresh = 0
    last_gf, changed = None, True
    while changed and rounds < n:
        gf = f[f]
        fresh += last_gf is None or bool((gf != last_gf).any())
        last_gf = gf
        u = np.full(n, _BIG, np.int64)
        np.minimum.at(u, rows, gf[cols])  # u[i] = min over neighbours of gf
        f1 = f.copy()
        np.minimum.at(f1, f, u)  # stochastic hooking
        nb = np.minimum(np.minimum(f1, u), gf)  # aggressive + shortcut
        changed = bool((nb != f).any())
        f, rounds = nb, rounds + 1
    jumps, changed = 0, True
    while changed:
        gf = f[f]
        changed = bool((gf != f).any())
        f, jumps = gf, jumps + 1
    return f.astype(np.int32), rounds, jumps, fresh


def _symmetric(n, pairs):
    a = np.array([p[0] for p in pairs], np.int64)
    b = np.array([p[1] for p in pairs], np.int64)
    return n, np.concatenate([a, b]), np.concatenate([b, a])


def _rmat9():
    from chipbench import graph

    n, rows, cols, _ = graph.rmat_graph(9, 16, 1)
    return n, rows, cols


def _path64():
    """Ids along the path are shuffled: labels travel far, many rounds."""
    ids = np.random.default_rng(11).permutation(64)
    return _symmetric(64, list(zip(ids, ids[1:])))


def _star():
    """Hub 17 of 48 vertices: one hook, then every round confirms."""
    return _symmetric(48, [(17, v) for v in range(48) if v != 17])


def _two_and_lonely():
    """A cycle of 15 and a clique of 9 among 41 vertices (odd: the 2 x 2
    grid's blocks carry padding), the other 17 isolated."""
    ids = np.random.default_rng(12).permutation(41)
    ring, clique = ids[:15], ids[15:24]
    pairs = list(zip(ring, np.roll(ring, 1)))
    pairs += [(u, v) for i, u in enumerate(clique) for v in clique[i + 1:]]
    return _symmetric(41, pairs)


def _warm_converged():
    """R-MAT from its own final labels: one round, which sweeps."""
    n, rows, cols = _rmat9()
    return n, rows, cols, reference(n, rows, cols)[0]


def _warm_stale():
    """What a refresh after insert-only deltas starts from: the final
    labels of ``_two_and_lonely``, on that graph with a bridge between
    its two pieces and an edge to a lonely vertex.  The piece that loses
    its label hooks in round 1, its far vertices shortcut in round 2,
    and round 3 confirms on round 2's sweep."""
    n, rows, cols = _two_and_lonely()
    start = reference(n, rows, cols)[0]
    ids = np.random.default_rng(12).permutation(41)
    _, a, b = _symmetric(n, [(ids[3], ids[20]), (ids[7], ids[30])])
    return n, np.concatenate([rows, a]), np.concatenate([cols, b]), start


#: name -> ``(n, rows, cols[, the start's labels])``; no start = ``iota``
CASES = {
    "rmat9": _rmat9,
    "path64": _path64,
    "star": _star,
    "two_and_lonely": _two_and_lonely,
    "warm_converged": _warm_converged,
    "warm_stale": _warm_stale,
}


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
@pytest.mark.parametrize("kind", ["ell", "coo"])
@pytest.mark.parametrize("name", list(CASES))
def test_a_round_reuses_the_sweep_whose_grandparents_it_reads(
        name, kind, shape):
    import jax

    if len(jax.devices()) < shape[0] * shape[1]:
        pytest.skip("the 2 x 2 grid needs four devices")
    n, rows, cols, start = (CASES[name]() + (None,))[:4]
    grid = Grid.make(*shape)
    ones = np.ones(len(rows), np.float32)
    M = (EllParMat.from_host_coo(grid, rows, cols, ones, n, n)
         if kind == "ell"
         else SpParMat.from_global_coo(grid, rows, cols, ones, n, n))
    f0 = None
    if start is not None:  # as ``dynamic/refresh.py`` builds it
        f0 = DistVec.from_global(grid, start, align="row").mask_padding(
            np.int32(_BIG))

    want, rounds, jumps, fresh = reference(n, rows, cols, start)
    out = cc.fastsv(M, f0)
    assert len(out) == 3
    assert np.array_equal(out[0].to_global(), want)
    assert (int(out[1]), int(out[2])) == (rounds, jumps)

    program = cc.cc_fastsv_ell if kind == "ell" else cc.cc_fastsv
    blocks, p_rounds, p_jumps, sweeps = program(
        M, None if f0 is None else f0.blocks)
    assert np.array_equal(np.asarray(blocks).reshape(-1)[:n], want)
    assert (int(p_rounds), int(p_jumps)) == (rounds, jumps)
    assert int(sweeps) == fresh
    assert 1 <= int(sweeps) <= rounds
    if name == "rmat9":  # hooks end, a round shortcuts, a round confirms
        assert int(sweeps) == rounds - 1
    if name == "warm_converged":
        assert int(sweeps) == rounds == 1
    if name == "warm_stale":
        assert (rounds, int(sweeps)) == (3, 2)
