"""Library call that multiplies the whole matrix by itself on a MESH, back
to back: ``entry(sr, A, A)`` on an ``SpParMat`` 2D-distributed over the
configuration's grid, one product in flight, each job closed by the
host's read of its digest.  ``library_product.py``'s loop and checks
(loaded from it, as it loads ``library_job``'s), with what a grid of
more than one tile changes: the matrix is uploaded tile by tile
(``SpParMat.from_global_coo(dep.grid, ...)``: a tile a chip), C stays
2D-distributed on the chips, and the LAST job's C is read back from
EVERY tile (``library_product.stored`` reads tile ``[0, 0]`` alone).

``mteps`` is the median over the jobs of the graph's undirected input
edges over one job's wall from launch to the host's digest; not reported
over fewer than four whole jobs.  A job reads nothing ``--seed`` draws:
the seed picks which jobs' digests are held to the reference
(``sqref.SQReference.check_digest``: the first, the last and
``check.sampled`` others); every other job's digest must equal the
first's.  The last job's C is held to the reference ENTRY FOR ENTRY, a
tile at a time: tile ``(i, j)``'s stored tuples, in the tile's own
coordinates, against the reference's block ``C[i*lr:(i+1)*lr,
j*lc:(j+1)*lc]`` (``check_entries``: the same coordinates, each once,
the same value at each).  The blocks tile the matrix, so that is the
whole comparison, nothing sampled; it sorts a quarter of the 120.9 M
entries at a time where the whole at once passes a minute of every
later check.  Integers: the limit of both is equality.

Mix parameters: as ``library_product``'s (``entry``, ``semiring``,
``job``, ``check``, ``trace``).
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import serving, sqmcost, sqref
from chipbench.spec import resolve


def stored_tiles(C) -> list[tuple]:
    """The stored tuples of every tile of an ``SpParMat``, on the host:
    ``(i, j, rows, cols, vals)`` a tile, in GLOBAL coordinates."""
    rows, cols, vals = (np.asarray(x) for x in (C.rows, C.cols, C.vals))
    lr, lc = C.local_rows, C.local_cols
    out = []
    for i in range(rows.shape[0]):
        for j in range(rows.shape[1]):
            keep = rows[i, j] < lr
            out.append((i, j, rows[i, j][keep] + i * lr,
                        cols[i, j][keep] + j * lc, vals[i, j][keep]))
    return out


class Block(sqref.SQReference):
    """One tile's block of a reference's C, as a reference of its own
    (``check_entries`` holds a tile's tuples to it)."""

    def __init__(self, side: int, C):
        self.n, self.C = int(side), C


def check_tiles(ref: sqref.SQReference, tiles: list[tuple],
                grid: tuple[int, int]) -> list[str]:
    """``tiles``: ``stored_tiles`` of a C on ``grid``.  Every tile held
    to the reference's own block, entry for entry; a tuple outside its
    tile's block is refused by its coordinates."""
    lr, lc = -(-ref.n // grid[0]), -(-ref.n // grid[1])
    side = max(lr, lc)
    problems = []
    if sorted(t[:2] for t in tiles) != [
            (i, j) for i in range(grid[0]) for j in range(grid[1])]:
        return [f"the last job's C: tiles {[t[:2] for t in tiles]} are "
                f"not the grid's {grid}"]
    for i, j, r, c, v in tiles:
        want = ref.C[i * lr:(i + 1) * lr, j * lc:(j + 1) * lc].tocsr()
        want.resize((side, side))
        want.sort_indices()
        r = np.asarray(r, np.int64) - i * lr
        c = np.asarray(c, np.int64) - j * lc
        if len(r) and (r.min() < 0 or r.max() >= lr
                       or c.min() < 0 or c.max() >= lc):
            problems.append(
                f"the last job's C, tile ({i}, {j}): a stored tuple lies "
                "outside the tile's block")
            continue
        bad = Block(side, want).check_entries(r, c, v)
        if bad:
            problems.append(f"the last job's C, tile ({i}, {j}): {bad}")
    return problems


def block_counts(ref: sqref.SQReference, rows, cols,
                 grid: tuple[int, int]) -> tuple:
    """From the REFERENCE and the input alone: A's stored entries by
    row block and by column block of the grid, and C's by tile."""
    lr, lc = -(-ref.n // grid[0]), -(-ref.n // grid[1])
    a_rows = np.bincount(np.asarray(rows) // lr, minlength=grid[0])
    a_cols = np.bincount(np.asarray(cols) // lc, minlength=grid[1])
    c_tiles = np.zeros(grid, np.int64)
    owner = ref.C.indices // lc
    for i in range(grid[0]):
        lo, hi = ref.C.indptr[i * lr], ref.C.indptr[min((i + 1) * lr, ref.n)]
        c_tiles[i] = np.bincount(owner[lo:hi], minlength=grid[1])
    return a_rows.tolist(), a_cols.tolist(), c_tiles.tolist()


def run(job) -> dict:
    mix = job.mix
    try:  # before the graph is loaded: a program without the entry
        fn, sr = resolve(mix["entry"]), resolve(mix["semiring"])
    except (ImportError, AttributeError) as e:
        raise SystemExit(
            f"chipbench: the program has no {mix['entry']!r} ({e}): the "
            "cell needs the sparse product's job entry that returns C "
            "and its digest"
        ) from e
    product = job.spec.load_module("drivers", "library_product")
    checked_jobs = job.spec.load_module("drivers", "library_job").checked_jobs
    dep = job.deploy()
    n = dep.n
    grid = (dep.grid.pr, dep.grid.pc)
    edges = len(dep.rows) // 2  # symmetrised, no loops: two nonzeros each

    from combblas_tpu.parallel.spmat import SpParMat

    t0 = time.perf_counter()
    A = SpParMat.from_global_coo(
        dep.grid, dep.rows, dep.cols,
        np.ones(len(dep.rows), np.float32), n, n)
    A.rows.block_until_ready()
    serving.log(f"SpParMat of {len(dep.rows)} nonzeros uploaded to "
                f"{grid[0]} x {grid[1]} tiles of capacity {A.capacity} in "
                f"{time.perf_counter() - t0:.1f} s")
    kept = [None]  # the one C on the mesh

    def one():
        """Launch a job; the host's read of its digest closes it."""
        kept[0] = None
        kept[0], digest = fn(sr, A, A, **mix["job"])
        return digest

    # warm-up: one untimed job (compiles, or fetches the programs from
    # the persistent cache)
    t0 = time.perf_counter()
    warm = one()
    warmup_s = time.perf_counter() - t0
    serving.log(f"warm-up job: {warmup_s:.1f} s, tier {warm.get('tier')} "
                f"under {warm.get('backend')}")

    c0 = job.compiles.count
    spans, walls, digests = [], [], []
    t_first = time.perf_counter()
    t_end = t_first + job.seconds
    if job.tracer:
        job.tracer.begin(t_first)
    while time.perf_counter() < t_end:
        w0, t0 = time.time(), time.perf_counter()
        digests.append(one())
        t1 = time.perf_counter()
        spans.append(("job", w0, w0 + (t1 - t0)))
        walls.append(t1 - t0)
    compiles = job.compiles.count - c0
    reduced, offset = job.tracer.finish() if job.tracer else (None, None)

    # checks, outside the window
    t0 = time.perf_counter()
    C = kept[0]
    tile_cap = int(C.capacity)
    tiles = stored_tiles(C)
    kept[0] = C = None
    t_read = time.perf_counter() - t0
    ref = sqref.SQReference(n, dep.rows, dep.cols)
    t_ref = time.perf_counter() - t0 - t_read
    picks = checked_jobs(job.seed, len(digests), int(mix["check"]["sampled"]))
    problems = product.check_jobs(ref, digests, picks, None)
    problems += check_tiles(ref, tiles, grid)
    stored = [len(t[2]) for t in tiles]
    serving.log(
        f"sq: the reference's C has {ref.digest['nnz']} entries of sum "
        f"{ref.digest['sum']} (the largest {ref.largest}) from "
        f"{ref.products} products of {ref.nnz_a} nonzeros, {edges} "
        f"undirected edges of {n} vertices; checked the digests of jobs "
        f"{picks} of {len(digests)} and the last job's {sum(stored)} "
        f"stored entries, read from {len(tiles)} tiles of "
        f"{' '.join(map(str, stored))} under capacity {tile_cap}, against "
        "it block by block (limit: equality) in "
        f"{time.perf_counter() - t0:.1f} s ({t_read:.1f} s the read, "
        f"{t_ref:.1f} s the reference)")
    serving.log(
        f"sq: the first job {digests[0]['nnz']} entries of sum "
        f"{digests[0]['sum']}; seconds by job: "
        + " ".join(f"{w:.3f}" for w in walls[:64])
        + (" ..." if len(walls) > 64 else ""))
    mteps = None
    if len(walls) >= product.LEAST_JOBS:
        mteps = float(np.median(edges / np.asarray(walls) / 1e6))
    else:
        problems.append(
            f"{len(walls)} whole jobs in the window: no median over fewer "
            f"than {product.LEAST_JOBS}")
    ctx = {
        "load_s": dep.load_s, "load_how": dep.how, "warmup_s": warmup_s,
        "trace": reduced, "trace_offset": offset, "host_spans": spans,
        "job_walls": walls,
        "least_bytes": sqmcost.sq_mesh_job_least_bytes(
            *block_counts(ref, dep.rows, dep.cols, grid)),
    }
    return {
        "attempted": len(digests),
        "failed": 0,
        "problems": problems,
        "compiles_in_window": compiles,
        "t_first_send": t_first,
        "values": {"mteps": mteps},
        "ctx": ctx,
    }
