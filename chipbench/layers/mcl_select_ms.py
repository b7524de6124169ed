"""Algorithms + local kernels: device time a job under the scope ``mcl.select`` (the hard
prune, the select's and the recovery's thresholds, the kept mask and the re-normalisation,
on a dense row block where it lies or on tuples): what a job pays for
``MCLPruneRecoverySelect`` (ms)."""

from chipbench import mclscopes


def read(ctx):
    return mclscopes.scope_ms(ctx, ("mcl.select",))
