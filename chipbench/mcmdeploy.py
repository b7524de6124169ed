"""Bringing a bipartite pattern up: ``deploy.deploy`` for the matching
cell's configuration (``law`` ``bipartite-rmat``, ``mcmgraph.py``).

``deploy.py`` makes a symmetric R-MAT and loads it through
``GraphEngine.from_coo``; this pattern is rectangular, one-directional
and wanted both ways with its column lists
(``models/matching.py:BipartiteEll``: an engine loads one ELL and,
serving nothing, no companion), so the operand comes from
``BipartiteEll.from_host_coo``, the program's own load, under the
program's own ``serve.load`` span.  Made anew in every run (the draw and
the host's bucket passes are set-up: 40 s of it at scale 20); no
snapshot is kept, so a run's set-up does not depend on the run before
it but for the compile cache.
"""

from __future__ import annotations

import time

from . import mcmgraph
from .deploy import log


class Deployment:
    """The loaded pattern: ``M`` (a ``BipartiteEll``) and the host COO
    the reference needs."""

    def __init__(self, cfg, grid, M, nr, nc, rows, cols, load_s):
        self.cfg, self.grid, self.M = cfg, grid, M
        self.nr, self.nc, self.rows, self.cols = nr, nc, rows, cols
        self.how, self.load_s = "built", load_s


def deploy_bipartite(cfg: dict) -> Deployment:
    """The configuration's pattern on its grid."""
    import jax

    from combblas_tpu.models.matching import BipartiteEll
    from combblas_tpu.parallel.grid import Grid

    assert cfg["law"] == "bipartite-rmat", cfg["law"]
    grid = Grid.make(*cfg["grid"])
    t0 = time.perf_counter()
    nr, nc, rows, cols = mcmgraph.bipartite_rmat(
        int(cfg["scale"]), int(cfg["edgefactor"]), int(cfg["graph_seed"]))
    log(f"built bipartite R-MAT scale {cfg['scale']}: {nr} x {nc}, "
        f"nnz={len(rows)} in {time.perf_counter() - t0:.1f} s")
    t1 = time.perf_counter()
    M = BipartiteEll.from_host_coo(grid, rows, cols, nr, nc)
    jax.block_until_ready(jax.tree_util.tree_leaves(M))
    log(f"buckets and lists, both ways, on the device in "
        f"{time.perf_counter() - t1:.1f} s")
    load_s = time.perf_counter() - t0
    log(f"deployment {cfg['name']}: built in {load_s:.1f} s")
    return Deployment(cfg, grid, M, nr, nc, rows, cols, load_s)
