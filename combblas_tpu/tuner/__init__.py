"""``combblas_tpu.tuner`` — measured-cost autotuner with persisted plans.

Three pieces (see docs/autotuning.md):

* :mod:`~combblas_tpu.tuner.config` — the ONE parser for the
  ``COMBBLAS_SPGEMM_*`` / plan-store env knobs, and the documented
  resolution precedence: **arg > store > env > heuristic**.
* :mod:`~combblas_tpu.tuner.store` — the schema-versioned JSONL plan
  store (``.plan_store/plans.jsonl`` next to the XLA compile cache):
  plans keyed by (shape bucket, density band, semiring, backend,
  grid/grid3) holding the measured tier/window/schedule choice.
* :mod:`~combblas_tpu.tuner.probe` — the opt-in micro-probe pass
  (``COMBBLAS_TUNER_PROBE=1``): on a store miss, time the admissible
  rungs on a bounded deterministic proxy and write the winner back.

``parallel.spgemm.spgemm_auto`` and ``parallel.mesh3d.spgemm3d``
consult the store.  The probe module is imported lazily (it pulls in
the kernels); config and store are dependency-light.
"""

from . import config  # noqa: F401
from .resolve import resolve_tier  # noqa: F401
from .store import (  # noqa: F401
    PlanKey,
    PlanRecord,
    PlanStore,
    SCHEMA,
    density_band,
    get_store,
    plan_key_from_counts,
    shape_bucket,
    spgemm3d_plan_key,
    spgemm_plan_key,
    spmm_plan_key,
)

__all__ = [
    "config",
    "resolve_tier",
    "PlanKey",
    "PlanRecord",
    "PlanStore",
    "SCHEMA",
    "density_band",
    "get_store",
    "plan_key_from_counts",
    "shape_bucket",
    "spgemm3d_plan_key",
    "spgemm_plan_key",
    "spmm_plan_key",
]
