"""Algorithms + local kernels: a wave's device time over the directed edges its
lanes' searches cross, by the REFERENCE's count (ns): the same work whatever
implements it, beside ``ell_ns_per_index``."""

from chipbench.deepwork import ns_per_edge as read  # noqa: F401
