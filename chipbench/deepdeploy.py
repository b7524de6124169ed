"""Bringing a random geometric graph up: ``deploy.deploy`` for a
configuration whose ``law`` is ``rgg`` (``rgggraph.py``).

``deploy.py`` can only make R-MAT; everything else of it is the graph
law's business no more than the program's: the snapshot's layout, its
loader, the ``Deployment`` the drivers are handed.  Those are reused.
The key adds ``rgggraph.py``'s own bytes to ``deploy.snapshot_key``,
which hashes ``graph.py`` alone.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

import numpy as np

from . import deploy, rgggraph
from .deploy import log


def snapshot_key(cfg: dict) -> str:
    h = hashlib.sha256(deploy.snapshot_key(cfg).encode())
    for path in (rgggraph.__file__, __file__):
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _build(cfg, grid, cache: str | None):
    from combblas_tpu.serve import GraphEngine
    from combblas_tpu.utils import checkpoint

    t0 = time.perf_counter()
    n, rows, cols, _ = rgggraph.rgg_graph(
        int(cfg["n_log2"]), int(cfg["graph_seed"]))
    log(f"built rgg n=2^{cfg['n_log2']}: nnz={len(rows)} in "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    engine = GraphEngine.from_coo(
        grid, rows, cols, n,
        keep_coo=bool(cfg.get("keep_coo", False)),
        kinds=tuple(cfg["kinds"]),
    )
    log(f"from_coo in {time.perf_counter() - t0:.1f} s")
    if cache is not None:
        tmp = cache + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        checkpoint.save_version(
            os.path.join(tmp, "version.npz"), engine.version)
        np.save(os.path.join(tmp, "rows.npy"), rows)
        np.save(os.path.join(tmp, "cols.npy"), cols)
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump({"n": n, "nnz": int(len(rows))}, f)
        shutil.rmtree(cache, ignore_errors=True)
        os.replace(tmp, cache)
        log(f"snapshot saved to {cache}")
    return engine, n, rows, cols


def deploy_rgg(cfg: dict, cache_root: str | None) -> deploy.Deployment:
    """``deploy.deploy`` with this law's generator: the configuration's
    graph on its grid, from the snapshot when there is one."""
    import jax

    from combblas_tpu.parallel.grid import Grid

    assert cfg["law"] == "rgg", cfg["law"]
    grid = Grid.make(*cfg["grid"])
    cache = None
    if cache_root is not None:
        cache = os.path.join(
            cache_root, f"{cfg['name']}-{snapshot_key(cfg)}")
    t0 = time.perf_counter()
    how, loaded = "built", None
    if cache is not None and os.path.isdir(cache):
        try:
            loaded = deploy._load(cfg, grid, cache)
            how = "snapshot"
        except Exception as e:  # any load error: build instead
            log(f"snapshot {cache} unusable ({type(e).__name__}: {e}); "
                "building")
    if loaded is None:
        loaded = _build(cfg, grid, cache)
    engine, n, rows, cols = loaded
    jax.block_until_ready([a for b in engine.E.buckets for a in b])
    load_s = time.perf_counter() - t0
    log(f"deployment {cfg['name']}: {how} in {load_s:.1f} s")
    return deploy.Deployment(cfg, grid, engine, n, rows, cols, how, load_s)
