"""Algorithms + local kernels: device time of one iteration of the FastSV program's
``cc.iter`` loop (one round: ``f[f]``, the one-lane select2nd-min sweep, the
scatter-min that hooks, two minimums and the fixed-point test), median over the
rounds of whole executions (ms)."""

from chipbench.ccscopes import round_ms as read  # noqa: F401
