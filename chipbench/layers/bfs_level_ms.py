"""Algorithms + local kernels: device time of one iteration of the served BFS
program's ``bfs.level`` loop, median over the levels of whole executions (ms)."""

from chipbench.scopes import level_ms as read  # noqa: F401
