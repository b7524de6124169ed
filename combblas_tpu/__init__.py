"""combblas_tpu — a TPU-native distributed sparse linear-algebra and
graph-analytics framework with the capabilities of CombBLAS.

Layer map (mirrors SURVEY.md §1, re-designed for JAX/XLA):

* ``semiring``   — trace-time semiring protocol (≈ Semirings.h functors).
* ``ops``        — local (single-tile) kernels on padded static-shape sparse
                   tiles: tuples/CSR/CSC formats, segment reductions, SpMV,
                   SpMSpV, SpGEMM, merge (≈ the sequential layer: dcsc/
                   SpDCCols/Friends/mtSpGEMM/MultiwayMerge/SpImpl).
* ``parallel``   — device-mesh grid, distributed matrices/vectors and the
                   SUMMA/SpMV/3D collective schedules (≈ CommGrid, SpParMat,
                   FullyDist*, ParFriends) expressed with shard_map +
                   psum/all_gather/ppermute/all_to_all over ICI.
* ``models``     — the application suite (BFS, CC, TC, PageRank, SSSP, MCL,
                   BC, MIS, matchings, RCM ≈ Applications/).
* ``utils``      — I/O (Matrix Market, Graph500 R-MAT generator),
                   profiling timers, checkpointing.
"""

from .semiring import (
    MAX_MIN,
    MIN_PLUS,
    OR_AND,
    PLUS_TIMES,
    SELECT2ND_MAX,
    SELECT2ND_MIN,
    STANDARD_SEMIRINGS,
    Semiring,
)
from .ops.tuples import SpTuples
from .ops.compressed import CSC, CSR

# Distributed layer (the reference's public surface).
from .parallel.grid import Grid
from .parallel.mesh3d import Grid3D, SpParMat3D, spgemm3d
from .parallel.dense import DenseParMat
from .parallel.ellmat import EllParMat
from .parallel.spmat import SpParMat
from .parallel.vec import DistVec
from .parallel.spgemm import (
    PhaseAdjustedWarning,
    block_spgemm,
    calculate_phases,
    choose_spgemm_tier,
    coo_has_duplicates,
    default_block_cols,
    default_block_rows,
    estimate_flops,
    estimate_nnz_upper,
    mem_efficient_spgemm,
    resolve_spgemm_backend,
    spgemm,
    spgemm_auto,
    spgemm_scan,
    spgemm_windowed,
    summa_spgemm_mxu,
    summa_spgemm_windowed,
)
from .parallel.spmv import dist_spmspv, dist_spmv, dist_spmv_masked
from .parallel.vec import DistMultiVec, concatenate
from .parallel.indexing import spasgn, subsref
from .semantic import SemanticGraph, filtered_bfs, filtered_mis

# Telemetry (metrics registry + span traces + JSONL export); see
# docs/observability.md. Zero-cost when disabled (the default).
from . import obs

# Query serving (GraphEngine + batched, backpressured Server); see
# docs/serving.md. Pure host-side layering over models/parallel —
# importing it costs nothing until an engine is built.
from . import serve

# Streaming graph mutation (DeltaBuffer + incremental apply_delta +
# warm-restart refresh); see docs/dynamic.md. Host-side like serve.
from . import dynamic

__version__ = "0.1.0"

__all__ = [
    # semirings
    "Semiring", "PLUS_TIMES", "MIN_PLUS", "MAX_MIN", "OR_AND",
    "SELECT2ND_MAX", "SELECT2ND_MIN", "STANDARD_SEMIRINGS",
    # local formats
    "SpTuples", "CSR", "CSC",
    # distributed objects
    "Grid", "Grid3D", "SpParMat", "SpParMat3D", "DenseParMat", "EllParMat",
    "DistVec",
    # distributed algebra
    "spgemm", "spgemm_scan", "spgemm_auto", "spgemm_windowed",
    "choose_spgemm_tier", "coo_has_duplicates", "resolve_spgemm_backend",
    "default_block_rows", "default_block_cols", "mem_efficient_spgemm",
    "block_spgemm", "spgemm3d", "summa_spgemm_mxu",
    "summa_spgemm_windowed", "PhaseAdjustedWarning",
    "estimate_flops", "estimate_nnz_upper", "calculate_phases",
    "dist_spmv", "dist_spmv_masked", "dist_spmspv", "subsref", "spasgn",
    "concatenate", "DistMultiVec",
    # semantic graphs
    "SemanticGraph", "filtered_bfs", "filtered_mis",
    # telemetry
    "obs",
    # query serving
    "serve",
    # streaming mutation lane
    "dynamic",
]
