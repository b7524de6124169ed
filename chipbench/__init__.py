"""chipbench — the benchmark of combblas_tpu on the chip.

One command runs one cell (``python3 -m chipbench.run``); what belongs to
one configuration, one traffic mix, one kind of traffic or one per-layer
metric is a file of its own, found by the names in ``BENCHMARK.json``
(see ``README.md`` here).  Importing this package loads no JAX.
"""
