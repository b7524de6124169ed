"""Algorithms + local kernels: device time of one iteration of the job's ``mcm.phase``
loop (one augmenting phase: alternating layers until a free column is found, the
candidates' chains chased twice for the winner selection, the surviving paths flipped),
median over the phases of whole executions (ms)."""

from chipbench.mcmscopes import phase_ms as read  # noqa: F401
