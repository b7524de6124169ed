"""Bringing a configuration up: the platform gate, the compile cache,
and the graph — built once per checkout, then loaded from a snapshot.

The graph goes in through the user entry point
(``GraphEngine.from_coo``); the first run of a configuration in a
checkout then ``save_version``s what was built under
``<path>/.cache/<config>-<key>/`` together with the host COO the
references need.  Later runs ``load_version`` it (bucket arrays as built:
no R-MAT, no dedup sort, no host bucket pass).  The key covers the
configuration file, this generator and every source file of the program,
so a changed program never loads its parent's snapshot; any load error
falls back to building.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
import time

import numpy as np

from . import graph
from .spec import CHECKOUT, HERE


def log(msg: str) -> None:
    print(f"[chipbench {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def rehearsal() -> bool:
    """``JAX_PLATFORMS=cpu`` given by name: the CPU rehearsal the tests
    use.  Nothing else lets this benchmark run off the chip."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def start_backend(chips: int) -> dict:
    """Start JAX, require the cell's chips, name the device.  Exits
    non-zero (and so prints no result) when the backend is not a TPU,
    unless the CPU was asked for by name."""
    import jax

    backend = jax.default_backend()
    devs = jax.devices()
    if backend != "tpu" and not (backend == "cpu" and rehearsal()):
        raise SystemExit(
            f"chipbench: backend is {backend!r}, not 'tpu' (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '<unset>')}); a CPU "
            "rehearsal needs JAX_PLATFORMS=cpu by name"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"chipbench: the cell needs {chips} chips, JAX sees {len(devs)}"
        )
    if backend == "tpu":
        # the persistent cache: JAX_COMPILATION_CACHE_DIR where set, else
        # the program's fixed <checkout>/.jax_cache.  A rehearsal keeps
        # none (cached XLA:CPU programs would land in the checkout).
        from combblas_tpu.utils import compile_cache

        compile_cache.enable_compile_cache()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def memory_peak_bytes() -> int:
    """Peak bytes on the fullest chip, where the backend reports it."""
    import jax

    peaks = [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()
    ]
    return max(peaks) if peaks else 0


# --------------------------------------------------------------------------
# snapshot key
# --------------------------------------------------------------------------


def snapshot_key(cfg: dict) -> str:
    h = hashlib.sha256()
    with open(cfg["_file"], "rb") as f:
        h.update(f.read())
    program = []
    for top, _, names in os.walk(os.path.join(CHECKOUT, "combblas_tpu")):
        program += [
            os.path.join(top, nm) for nm in names if nm.endswith(".py")
        ]
    for path in sorted(program) + [os.path.join(HERE, "graph.py"), __file__]:
        h.update(os.path.relpath(path, CHECKOUT).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


# --------------------------------------------------------------------------
# build / load
# --------------------------------------------------------------------------


class Deployment:
    """A loaded configuration: the engine, and the host COO for the
    references."""

    def __init__(self, cfg, grid, engine, n, rows, cols, how, load_s):
        self.cfg = cfg
        self.grid = grid
        self.engine = engine
        self.n = n
        self.rows = rows
        self.cols = cols
        self.how = how  # "snapshot" | "built"
        self.load_s = load_s
        self.deg = graph.degrees(rows, n)
        self._ref = None

    def reference(self) -> graph.Reference:
        if self._ref is None:
            self._ref = graph.Reference(self.n, self.rows, self.cols)
        return self._ref


def _build(cfg, grid, cache: str | None):
    from combblas_tpu.serve import GraphEngine
    from combblas_tpu.utils import checkpoint

    t0 = time.perf_counter()
    n, rows, cols, _ = graph.rmat_graph(
        int(cfg["scale"]), int(cfg["edgefactor"]), int(cfg["graph_seed"])
    )
    weights = (
        graph.edge_weights(rows, cols, int(cfg["graph_seed"]))
        if "sssp" in cfg["kinds"] else None
    )
    log(f"built R-MAT scale {cfg['scale']}: n={n} nnz={len(rows)} in "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    engine = GraphEngine.from_coo(
        grid, rows, cols, n, weights=weights,
        keep_coo=bool(cfg.get("keep_coo", False)),
        kinds=tuple(cfg["kinds"]),
    )
    log(f"from_coo in {time.perf_counter() - t0:.1f} s")
    if cache is not None:
        t0 = time.perf_counter()
        tmp = cache + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        checkpoint.save_version(
            os.path.join(tmp, "version.npz"), engine.version
        )
        np.save(os.path.join(tmp, "rows.npy"), rows)
        np.save(os.path.join(tmp, "cols.npy"), cols)
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump({"n": n, "nnz": int(len(rows))}, f)
        shutil.rmtree(cache, ignore_errors=True)
        os.replace(tmp, cache)
        log(f"snapshot saved to {cache} in "
            f"{time.perf_counter() - t0:.1f} s")
    return engine, n, rows, cols


def _load(cfg, grid, cache: str):
    from combblas_tpu.serve import GraphEngine
    from combblas_tpu.utils import checkpoint

    with open(os.path.join(cache, "meta.json")) as f:
        meta = json.load(f)
    rows = np.load(os.path.join(cache, "rows.npy"))
    cols = np.load(os.path.join(cache, "cols.npy"))
    version = checkpoint.load_version(
        os.path.join(cache, "version.npz"), grid,
        writable=bool(cfg.get("keep_coo", False)),
    )
    engine = GraphEngine(grid, version=version, kinds=tuple(cfg["kinds"]))
    return engine, int(meta["n"]), rows, cols


def deploy(cfg: dict, cache_root: str | None) -> Deployment:
    """The configuration's graph on its grid, from the snapshot when
    there is one."""
    import jax

    from combblas_tpu.parallel.grid import Grid

    grid = Grid.make(*cfg["grid"])
    cache = None
    if cache_root is not None:
        cache = os.path.join(
            cache_root, f"{cfg['name']}-{snapshot_key(cfg)}"
        )
    t0 = time.perf_counter()
    how = "built"
    loaded = None
    if cache is not None and os.path.isdir(cache):
        try:
            loaded = _load(cfg, grid, cache)
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
    return Deployment(cfg, grid, engine, n, rows, cols, how, load_s)
