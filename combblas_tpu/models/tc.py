"""Triangle counting — masked SpGEMM (≈ Applications/TC.cpp).

The reference computes ``L = tril(A)``, ``C = (L * L) .* L`` with
``Mult_AnXBn_Synch<PlusTimesSRing>`` + ``EWiseMult``, then sums C
(``TC.cpp:104-116``).  Here: the SUMMA SpGEMM over the mesh, the mask as
``ewise_mult``, and the final sum as a column reduce + vector fold — each
triangle {i>j>k} contributes C[i,j] += 1 via the wedge through k.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
from ..ops.spgemm import (
    combine_hilo,
    coo_sort_dedup as _coo_sort_dedup,
    front_pack_pairs,
    harvest_path,
    pack_support_bits,
    popcount_pair_counts,
)
from ..semiring import PLUS_TIMES
from ..parallel.spgemm import spgemm, summa_spgemm
from ..parallel.spmat import SpParMat, ones_f32

#: Above this dimension the dense [n, n] mask product would exceed a few
#: GB of HBM; the sparse SUMMA path takes over.
DENSE_MAX_DIM = 32768


def _tc_dense(rows, cols, n: int) -> jax.Array:
    """One-launch dense TC: sum((L·L) ⊙ L) on the MXU.

    bf16 0/1 inputs are exact; per-cell wedge counts < n < 2^24 are exact
    in the f32 accumulator.  No sparse extraction at all — the mask IS
    the (tiny) output support, so the whole computation is matmul + two
    elementwise passes.

    Returns an int32 [2] (hi, lo) split of the global triangle count:
    the GLOBAL total can exceed 2^31 for dense graphs within
    ``DENSE_MAX_DIM`` (a complete graph at n~3000 already would) while
    int64 is unavailable without x64 mode (ADVICE r4).  Per-row sums are
    int32-exact (< n^2 <= 2^30); each splits into 15-bit halves whose
    column sums stay < n * 2^15 <= 2^30.  ``_tc_combine`` reassembles the
    exact Python int (range 2^45 — beyond any n <= 32768 count).
    """
    npad = -(-n // 128) * 128
    keep = rows > cols  # strict lower triangle, loops dropped
    r = jnp.where(keep, rows, npad)
    c = jnp.where(keep, cols, npad)
    d = jnp.zeros((npad, npad), jnp.bfloat16)
    d = d.at[r, c].set(jnp.bfloat16(1.0), mode="drop")
    wedges = jnp.dot(d, d, preferred_element_type=jnp.float32)
    masked = wedges * d.astype(jnp.float32)
    # cast per CELL before the row sum: cells are f32-exact (< n < 2^24)
    # but an f32 row accumulation would round past 2^24; int32 row sums
    # are exact below n^2 <= 2^30
    rowsum = jnp.sum(masked.astype(jnp.int32), axis=1)
    hi = jnp.sum(rowsum >> 15)
    lo = jnp.sum(rowsum & 0x7FFF)
    return jnp.stack([hi, lo])


#: Edge-harvest ceiling: the symmetric adjacency must fit HBM,
#: bit-packed n^2/8 bytes (8.6 GB at n = 262144, i.e. scale 18 on the
#: 16 GB chip).
EDGE_HARVEST_BITS_MAX_DIM = 262144


# _coo_sort_dedup now lives in ops/spgemm.py (coo_sort_dedup) — it is the
# shared dedup front of every bit-packed kernel, imported above.

#: The ``jax.named_scope`` names of the bit-packed harvest program
#: (``tc_edgeharvest_bits``), outermost first.  The scan walks the kept
#: pairs, chunk-padded (``front_pack_pairs`` brings them to the front
#: under ``tc.dedup``), one step a chunk.  ``gather`` and ``popcount``
#: are set by ``ops/spgemm.py:popcount_pair_counts`` inside a step of
#: its ``jnp`` loop, where they read ``tc.harvest/gather`` and
#: ``tc.harvest/popcount``; the fused step (``harvest_path``: a TPU and
#: whole-tile rows) is one kernel, ``pair_popcount``, straight under
#: ``tc.harvest``, and carries neither.  Trace-time metadata only: the
#: device trace's per-scope times are read by these names
#: (docs/observability.md "Named scopes"), so a rename is a change of
#: yardstick.
TC_SCOPES = (
    # the (row, col) sort of every stored slot, which carries the list
    # itself (no permutation, no gather through one), the repeat mask,
    # and the sort that brings the kept pairs to the front
    "tc.dedup",
    # the packed table written: on the fused path one kernel (pack_rows)
    # that assembles every row on the chip and stores it once, behind the
    # fill and the binary searches that find a group's slots; elsewhere
    # the zero fill + scatter-add of one bit a kept nonzero
    "tc.pack",
    "tc.harvest",  # the whole scan over chunks of the kept row pairs
    "gather",  # jnp step: two row gathers of [chunk, n/32] words
    "popcount",  # jnp step: the AND, population count and weighted sum
)


#: Pairs a step of the one-device scan walks; the pair list is padded to
#: it, so ``pairs`` is a multiple (what a step FETCHES at once is the
#: kernel's own, ``ops/spgemm.py:HARVEST_GROUP``).
HARVEST_CHUNK = 8192


def _tc_edge_harvest_bits(rows, cols, n: int, chunk: int = HARVEST_CHUNK):
    """Bit-packed edge-harvest TC: the adjacency as a [n, n/32] uint32
    bitmask; each edge's common-neighbor count is popcount(row_i & row_j).

    TC only needs (A·A)[i,j] ON the edges: for each undirected edge
    (i>j), |N(i) ∩ N(j)| is the number of triangles through that edge,
    so 3·T = Σ_{edges i>j} |N(i) ∩ N(j)| — two row loads an edge, bound
    by row-gather traffic (8 KB/row at n = 64K), against the dense
    wedge product's 2n^3 FLOPs.  Reference role: the masked Mult_AnXBn
    of TC.cpp:104-116, redesigned output-driven for a chip with no
    scatter unit.  Packing sets bit c mod 32 of word (r, c div 32) once
    a kept slot (``pack_support_bits``: rows assembled on the chip and
    written once on the fused path, a scatter-ADD elsewhere: the input
    COO is dedup'd, so add ≡ bitwise-or, each bit lands exactly once).

    The scan walks the pairs it counts: the kept slots (strict lower
    triangle, first of a run of repeats) are brought to the front of
    the pair list in their row-sorted order, and the scan runs the
    ``ceil(edges / chunk)`` steps that hold one.  The table's shape and
    the step follow ``ops/spgemm.py:harvest_path``: on a TPU with n a
    multiple of 32,768 a row is whole tiles and one kernel fetches and
    counts a pair's rows; elsewhere plain rows and the ``jnp`` step.

    Returns ``(hilo, pairs, edges)``: the (hi, lo) int32 split of 3·T
    (``combine_hilo`` // 3 gives T; 3·T can exceed 2^31 — same split
    rationale as ``_tc_dense``), the pair slots the scan walks (the kept
    pairs, chunk-padded: ``steps * chunk``; 0 where nothing is kept)
    and the pairs of weight 1 (the undirected edges counted).
    """
    # ON-DEVICE DEDUP (duplicate COO entries would double-add a bit,
    # carrying into the NEXT bit and corrupting the adjacency, and the
    # edge walk would harvest their common neighbors twice): mask
    # repeats, zero their bit contribution AND their edge weight.
    with jax.named_scope("tc.dedup"):
        rows, cols, dup = _coo_sort_dedup(rows, cols)
        loops = rows == cols
        keep = (rows > cols) & ~dup
        er, ec, ew, edges = front_pack_pairs(keep, rows, cols, chunk=chunk)
    with jax.named_scope("tc.pack"):
        r_all = jnp.where(loops | dup, n, rows)  # dropped (mode="drop")
        bits = pack_support_bits(
            r_all, cols, n, n, assume_unique=True,
            row_tiles=harvest_path(-(-n // 32)) == "fused")
    with jax.named_scope("tc.harvest"):
        hilo = popcount_pair_counts(
            bits, bits, er, ec, ew, chunk=chunk, count=edges)
    return hilo, -(-edges // chunk) * chunk, edges


@partial(jax.jit, static_argnames=("n",))
def tc_edgeharvest_bits(rows, cols, n: int):
    """The ONE program of a ``tc_job`` (``jit_tc_edgeharvest_bits`` in a
    device trace): a one-tile ``SpParMat``'s ``[1, 1, cap]`` rows and
    columns in, ``(hilo, pairs, edges)`` out."""
    return _tc_edge_harvest_bits(rows[0, 0], cols[0, 0], n)


#: Exact host-side total from a (hi, lo) split — shared with the other
#: bit-packed kernels (ops/spgemm.py).
_tc_combine = combine_hilo


@partial(jax.jit, static_argnames=("chunk",))
def _tc_edge_harvest_dist(A: SpParMat, chunk: int = 8192) -> jax.Array:
    """DISTRIBUTED bit-packed edge-harvest TC: the output-support oracle
    tier on a p x p mesh.

    Each device packs its tile of the symmetric adjacency into a
    [local_rows, lc/32] bitmask over its own LOCAL columns, gathers the
    packed tiles along its grid row and CONCATENATES them on the word
    axis (column tiles cover disjoint, word-aligned global column
    ranges — requires ``local_cols % 32 == 0``, which ``triangle_count``
    enforces), and fetches its grid COLUMN's row-block mask from the
    transpose-partner device with one ``ppermute`` (the mesh transpose,
    SpParMat.transpose's route).  Every device then harvests ONLY ITS
    OWN tile's strict-lower edges — the edge mask is already
    distributed — with ``popcount_pair_counts`` over the two local
    tables, fed as the one-device kernel feeds it (``front_pack_pairs``,
    then as many steps as the tile's own kept pairs fill: each device
    loops its own count, no collective is inside the loop), and the
    (hi, lo) partial sums ``psum`` into the global
    3·T count.  Local-column packing keeps the gather transient at the
    table's own n²/(8p) bytes (packing full-width [lr, n/32] tiles and
    OR-folding would transiently materialize p copies = n²/8 — the
    single-shard footprint the distribution exists to avoid).
    """
    from ..parallel.grid import COL_AXIS, ROW_AXIS
    from ..parallel.spmat import TILE_SPEC
    from jax.sharding import PartitionSpec as P

    grid = A.grid
    p = grid.pr
    assert grid.is_square, "edge-harvest TC needs a square grid"
    n = A.nrows
    lr, lc = A.local_rows, A.local_cols
    assert lr == lc, "square blocking required (symmetric adjacency)"
    assert lc % 32 == 0 or p == 1, (
        f"distributed edge-harvest needs word-aligned column tiles "
        f"(local_cols {lc} % 32 != 0); pad the matrix or use "
        "kernel='sparse'"
    )
    nw_loc = -(-lc // 32)

    def body(ar, ac):
        rows, cols = ar[0, 0], ac[0, 0]
        ri = lax.axis_index(ROW_AXIS)
        ci = lax.axis_index(COL_AXIS)
        valid = rows < lr
        grows = jnp.where(valid, rows + ri * lr, n)
        gcols = jnp.where(valid, cols + ci * lc, n)
        grows, gcols, dup = _coo_sort_dedup(grows, gcols)
        loops = grows == gcols
        # EXPLICIT drop mask, then localize: sentinel ARITHMETIC is a
        # trap here — with ceil-blocking over-cover (n % lr != 0) the
        # n-sentinel minus the last block's offset lands back INSIDE
        # [0, lr), and pack's scatter-ADD would pile every padded slot
        # onto one cell, carrying across bits.  Dropped slots get the
        # row sentinel lr directly (>= nrows ⇒ pack drops them whatever
        # their column).
        drop = dup | loops | (grows >= n)
        rloc = jnp.where(drop, lr, grows - ri * lr)
        cloc = jnp.where(drop, lc, gcols - ci * lc)
        bits_tile = pack_support_bits(
            rloc, cloc, lr, nw_loc * 32, assume_unique=True
        )
        # concat along the word axis: grid-row tiles cover disjoint,
        # word-aligned global column ranges (lc % 32 == 0), so the
        # gathered [p, lr, nw_loc] blocks ARE the full row mask
        g = lax.all_gather(bits_tile, COL_AXIS)
        rowbits = jnp.transpose(g, (1, 0, 2)).reshape(lr, p * nw_loc)
        # transpose partner: device (r, c) <- (c, r) row-block mask
        colbits = lax.ppermute(
            rowbits, (ROW_AXIS, COL_AXIS), grid.transpose_perm()
        )
        keep = (~dup) & (grows < n) & (grows > gcols)
        er, ec, ew, kept = front_pack_pairs(
            keep, grows - ri * lr, gcols - ci * lc, chunk=chunk)
        hilo = popcount_pair_counts(
            rowbits, colbits, er, ec, ew, chunk=chunk, count=kept)
        return lax.psum(lax.psum(hilo, ROW_AXIS), COL_AXIS)

    return jax.shard_map(
        body,
        mesh=grid.mesh,
        in_specs=(TILE_SPEC,) * 2,
        out_specs=P(),
        check_vma=False,
    )(A.rows, A.cols)


def tc_job(A: SpParMat) -> tuple[int, int, int]:
    """One whole triangle count of the simple undirected graph ``A``
    (symmetric nonzero structure; loops and repeated entries are masked
    on the device) as GAP times a trial: from the stored edge list to
    the exact count, nothing kept from job to job.  Runs the bit-packed
    edge harvest, the kernel ``triangle_count``'s ``auto`` picks on one
    device past the dense product's ceiling (``DENSE_MAX_DIM`` < n <=
    ``EDGE_HARVEST_BITS_MAX_DIM``) and ``kernel="edgeharvest"`` names
    at any n under that cap.

    Returns ``(triangles, pairs, edges)``, Python ints, all three from
    the program's own outputs (so they come back with telemetry off):
    the exact count, the pair slots the harvest walked (the kept pairs,
    chunk-padded: two rows fetched each) and the pairs of weight 1 (the
    undirected edges).

    Eager wrapper: the readback of the three closes the job."""
    n = max(A.nrows, A.ncols)
    if A.grid.size != 1 or n > EDGE_HARVEST_BITS_MAX_DIM:
        raise ValueError(
            "edgeharvest needs the dense adjacency in one chip's HBM: "
            f"one device and n <= {EDGE_HARVEST_BITS_MAX_DIM}, got "
            f"{A.grid.size} devices and n = {n}"
        )
    hilo, pairs, edges = jax.device_get(
        tc_edgeharvest_bits(A.rows, A.cols, n=A.nrows)
    )
    triangles, pairs, edges = combine_hilo(hilo) // 3, int(pairs), int(edges)
    if obs.ENABLED:
        # after the call, as models/cc.py:fastsv publishes: the first
        # traced job pays for the program as an untraced one does
        obs.opnames.publish_once(
            ("tc_edgeharvest_bits", A.nrows, A.rows.shape),
            lambda: tc_edgeharvest_bits.lower(
                A.rows, A.cols, n=A.nrows).compile().as_text(),
        )
        obs.count("models.tc.jobs")
        obs.count("models.tc.pairs", pairs)
        obs.count("models.tc.edges", edges)
        obs.count("models.tc.triangles", triangles)
        # which step walked the pairs and which pack wrote the table:
        # one predicate (whole-tile rows where a kernel runs)
        path = harvest_path(-(-A.nrows // 32))
        obs.count("models.tc.harvest_steps", pairs // HARVEST_CHUNK, path=path)
        obs.count("models.tc.pack",
                  path="rows" if path == "fused" else "scatter")
    return triangles, pairs, edges


#: The kernels ``triangle_count`` can be told to run.
TC_KERNELS = ("auto", "dense", "edgeharvest", "sparse")


def triangle_count(A: SpParMat, kernel: str = "auto") -> int:
    """Number of triangles in the simple undirected graph A (symmetric,
    loop-free nonzero structure).

    ``kernel="dense"`` (or "auto" on a single shard with n <=
    ``DENSE_MAX_DIM``) runs the round-4 one-launch MXU path: on the
    target chip the sparse masked SpGEMM pays the ~22 M/s random-memory
    wall (6.31 s at scale 14, round-3 notes) while the dense product runs
    at 13.3 TFLOP/s and the mask removes any need for sparse extraction.
    ``kernel="edgeharvest"`` (the bit-packed output-support tier) now
    works on MULTI-DEVICE square grids too (round 6,
    ``_tc_edge_harvest_dist``): per-device row-block bitmasks, OR along
    grid rows, transpose-partner ppermute, psum'd popcount partials —
    and "auto" picks it for sharded graphs within the n²/(8p) per-device
    mask budget.  ``kernel="sparse"`` forces the distributed
    masked-SpGEMM path (TC.cpp:104-116 flow), the fallback beyond the
    mask budget and on non-square grids; NOTE it expects a deduplicated
    edge list (values are wedge counts), while the harvest kernels
    dedup on device.  Any other ``kernel`` is a ``ValueError``.
    """
    if kernel not in TC_KERNELS:
        raise ValueError(
            f"kernel must be one of {', '.join(TC_KERNELS)}; "
            f"got {kernel!r}"
        )
    p = A.grid.pr
    # distributed bitmask budget: two n²/(8p)-byte tables per device must
    # fit the single-shard kernel's one-table HBM envelope
    dist_bits_cap = int(EDGE_HARVEST_BITS_MAX_DIM * (p / 2) ** 0.5)
    if kernel == "auto":
        if A.grid.size == 1 and max(A.nrows, A.ncols) <= DENSE_MAX_DIM:
            kernel = "dense"
        elif (
            A.grid.size == 1
            and max(A.nrows, A.ncols) <= EDGE_HARVEST_BITS_MAX_DIM
        ) or (
            A.grid.size > 1
            and A.grid.is_square
            and A.local_cols % 32 == 0  # word-aligned tile concat
            and max(A.nrows, A.ncols) <= dist_bits_cap
        ):
            kernel = "edgeharvest"
        else:
            kernel = "sparse"
    if kernel == "dense":
        t = A.local_tile(A.rows, A.cols, A.vals, A.nnz)
        return _tc_combine(
            jax.jit(_tc_dense, static_argnums=2)(t.rows, t.cols, A.nrows)
        )
    if kernel == "edgeharvest":
        if obs.ENABLED:
            obs.count("spgemm.auto.tier", tier=kernel, sr="plus_times")
        if A.grid.size > 1:
            if max(A.nrows, A.ncols) > dist_bits_cap:
                raise ValueError(
                    "distributed edgeharvest needs two n^2/(8p)-byte "
                    f"bitmasks per device: n <= {dist_bits_cap} on this "
                    f"{p}x{p} grid, got {max(A.nrows, A.ncols)}"
                )
            return combine_hilo(_tc_edge_harvest_dist(A)) // 3
        return tc_job(A)[0]
    L = A.remove_loops().tril(strict=True).apply(ones_f32)
    B = spgemm(PLUS_TIMES, L, L)  # B[i,j] = # wedges i->k->j with i>k>j
    C = B.ewise_mult(L)  # keep wedge counts only where edge (i,j) closes
    colsums = C.reduce(PLUS_TIMES, axis="rows")
    return int(colsums.reduce(PLUS_TIMES))
