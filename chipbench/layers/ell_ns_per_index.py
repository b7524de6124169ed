"""Algorithms + local kernels: device self time under ``ell.bucket<i>/gather`` and
``/fold`` inside the loops the program tallies (``bfs.level``, ``sssp.round``,
``bc.forward`` + ``bc.backward``; kernel 3's parents pass sweeps outside the tally) of
one execution of the dominant served program, over the mean ``slots`` its width's
batches gathered by their stage records (ns an index; one chip: a mesh reads None)."""

from chipbench.ellwork import ns_per_index as read  # noqa: F401
