"""Algorithms + local kernels: device time under ``bfs.level`` (``bfs.push``
inside it included) of one wave over the levels the wave ran (us)."""

from chipbench.deepwork import level_us as read  # noqa: F401
