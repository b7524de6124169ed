"""Algorithms + local kernels: slots of the degree classes a served batch's sweeps
SKIPPED over all the slots its sweeps stood before, the busiest tile's (labels
``slots_skipped`` and ``slots`` of the same stage records as ``ell_mslots_per_batch``,
%)."""

from chipbench.ellwork import skipped_share as read  # noqa: F401
