"""Grid — the device-mesh analog of the reference's CommGrid.

The reference builds a √p×√p process grid with row/column/diagonal MPI
subcommunicators via ``MPI_Comm_split``
(``include/CombBLAS/CommGrid.h:44-166``, ``src/CommGrid.cpp:37-101``).  The
TPU-native equivalent is a ``jax.sharding.Mesh`` with named axes: a
"communicator" is just an axis name passed to a collective inside
``shard_map`` —

* rowWorld  (ranks sharing a grid row)    ⇒ collectives over axis ``"c"``
* colWorld  (ranks sharing a grid column) ⇒ collectives over axis ``"r"``
* diagWorld / complement-rank pair exchange (``GetComplementRank``,
  CommGrid.h:99) ⇒ ``lax.ppermute`` with the transpose permutation over
  ``("r", "c")``
* world ⇒ collectives over ``("r", "c")``

Owner math: the reference gives every process ⌊m/pr⌋ rows with the remainder
on the last row of processes (``SpParMat.cpp:5076-5104``).  XLA wants equal
static tile shapes, so we instead pad the global dims to ceil-multiples and
give every tile exactly ``ceil(m/pr) × ceil(n/pc)`` — owner of global row r
is simply ``r // local_rows``.  This changes only the internal layout, never
a computed result.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROW_AXIS = "r"  # varies over grid rows  → collectives here act per grid-column (colWorld)
COL_AXIS = "c"  # varies over grid cols  → collectives here act per grid-row (rowWorld)
LAYER_AXIS = "l"  # 3D grids (CommGrid3D fiberWorld analog)


@dataclasses.dataclass(frozen=True)
class Grid:
    """A 2D pr×pc device grid (≈ CommGrid). Static trace-time object."""

    mesh: Mesh

    @staticmethod
    def make(pr: int, pc: int, devices=None) -> "Grid":
        if devices is None:
            devices = jax.devices()[: pr * pc]
        if len(devices) < pr * pc:
            raise ValueError(f"need {pr * pc} devices, have {len(devices)}")
        arr = np.asarray(devices[: pr * pc]).reshape(pr, pc)
        return Grid(mesh=Mesh(arr, (ROW_AXIS, COL_AXIS)))

    @staticmethod
    def make_default(n_devices: int | None = None) -> "Grid":
        """Squarest grid over the available devices (≈ CommGrid's √p×√p)."""
        n = n_devices if n_devices is not None else len(jax.devices())
        pr = int(math.sqrt(n))
        while n % pr:
            pr -= 1
        return Grid.make(pr, n // pr)

    @property
    def pr(self) -> int:
        return self.mesh.shape[ROW_AXIS]

    @property
    def pc(self) -> int:
        return self.mesh.shape[COL_AXIS]

    @property
    def size(self) -> int:
        return self.pr * self.pc

    @property
    def is_square(self) -> bool:
        return self.pr == self.pc

    def transpose_perm(self) -> list[tuple[int, int]]:
        """ppermute pairs sending (i,j)'s data to (j,i) over ("r","c").

        The complement-rank exchange of ``CommGrid::GetComplementRank``
        (CommGrid.h:99) used by vector transpose and matrix Transpose.
        Requires a square grid.
        """
        assert self.is_square, "transpose exchange needs pr == pc"
        p = self.pr
        return [(i * p + j, j * p + i) for i in range(p) for j in range(p)]

    # --- owner math (ceil-blocked; see module docstring) ------------------

    def local_rows(self, nrows: int) -> int:
        return -(-nrows // self.pr)

    def local_cols(self, ncols: int) -> int:
        return -(-ncols // self.pc)

    def row_owner(self, nrows: int, gr):
        return gr // self.local_rows(nrows)

    def col_owner(self, ncols: int, gc):
        return gc // self.local_cols(ncols)

    # --- sharding helpers -------------------------------------------------

    def tile_sharding(self) -> NamedSharding:
        """[pr, pc, ...] arrays: leading dims map to mesh axes."""
        return NamedSharding(self.mesh, P(ROW_AXIS, COL_AXIS))

    def row_aligned_sharding(self) -> NamedSharding:
        """[pr, L] vector blocks: block i on grid-row i, replicated over cols."""
        return NamedSharding(self.mesh, P(ROW_AXIS))

    def col_aligned_sharding(self) -> NamedSharding:
        """[pc, L] vector blocks: block j on grid-col j, replicated over rows."""
        return NamedSharding(self.mesh, P(COL_AXIS))

    def __hash__(self):
        return hash((Grid, self.mesh))

    def __eq__(self, other):
        return isinstance(other, Grid) and self.mesh == other.mesh
