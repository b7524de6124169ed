"""Algorithms + local kernels: the BUSIEST device's busy time inside one whole
sparse-product job on the mesh (the programs of its symbolic pass, its numeric phase,
its pack and its digest together, between the start and the end of the program's own
``spgemm.job`` annotation: ``sqmscopes.py``), mean over the jobs the profiled slice
holds whole on every device (ms).  A job's wall waits for the slowest chip."""

from chipbench.sqmscopes import device_ms as read  # noqa: F401
