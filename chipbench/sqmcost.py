"""The bytes one sparse product ON A MESH must move through the fullest
chip.  ``sqcost.py``'s least work, for a job whose operands and result
are 2D-distributed: computed from the reference's counts, a LOWER bound
whatever implements the product."""

from __future__ import annotations

from chipbench.sqcost import ENTRY_BYTES


def sq_mesh_job_least_bytes(a_row_blocks, a_col_blocks, c_tiles) -> int:
    """One job ``C = A @ A`` on a ``pr x pc`` grid, as the chip that
    must move most pays: chip ``(i, j)`` computes tile ``(i, j)`` of C
    from A's row block ``i`` and A's column block ``j`` (SUMMA: every
    stage's operand tile passes through it once, its own and the ones it
    receives) and writes its tile of C once, 12 B an entry.
    ``a_row_blocks[i]`` / ``a_col_blocks[j]``: A's stored entries by
    row / column block; ``c_tiles[i][j]``: C's by tile.  It charges
    NOTHING to what an implementation adds (dense operands, a dense
    product, an extraction's passes, a pack), and nothing to the
    interconnect: a share of the HBM roofline computed from it cannot
    pass 100% however the product is made."""
    return ENTRY_BYTES * max(
        a_row_blocks[i] + a_col_blocks[j] + c_tiles[i][j]
        for i in range(len(a_row_blocks))
        for j in range(len(a_col_blocks)))
