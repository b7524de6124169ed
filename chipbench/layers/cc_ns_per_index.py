"""Algorithms + local kernels: device self time of one FastSV execution under
``ell.bucket<i>/gather`` and ``/fold`` (inside ``cc.spmv``) over the slots the job's
sweeps gathered by the program's own count (ns an index)."""

from chipbench.ellwork import cc_ns_per_index as read  # noqa: F401
