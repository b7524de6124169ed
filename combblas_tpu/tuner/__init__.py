"""``combblas_tpu.tuner`` — the environment parser of the serve,
dynamic, obs and shard knobs (:mod:`~combblas_tpu.tuner.config`).

Nothing here chooses a kernel: a product's tier comes from an argument
or from the operands' counts (``parallel.spgemm.choose_spgemm_tier``).
The package keeps its path for its importers; its name is a debt
(ROADMAP.md, Design).
"""

from . import config  # noqa: F401

__all__ = ["config"]
