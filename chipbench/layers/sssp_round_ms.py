"""Algorithms + local kernels: device time of one iteration of the served kernel-3
program's ``sssp.round`` loop (one whole min-plus sweep and the state's update),
median over the rounds of whole executions (ms)."""

from chipbench.k3scopes import round_ms as read  # noqa: F401
