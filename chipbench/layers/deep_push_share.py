"""Algorithms + local kernels: levels the served BFS took as a walk of the
frontier's columns (``serve.bfs.levels{mode=push}``) over all levels it ran (%)."""

from chipbench.deepwork import push_share as read  # noqa: F401
