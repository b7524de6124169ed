"""Algorithms + local kernels: the least bytes a wave's searches must move
(``deepcost.bfs_search_least_bytes``: 4 B a directed edge, 8 B a vertex) over the
chip's peak HBM bandwidth, over the wave's device time (%)."""

from chipbench.deepwork import hbm_share as read  # noqa: F401
