"""Algorithms + local kernels: device time a job under the scopes ``sq.extract`` (from
every dense window product back to tuples; the sort-and-fold of a tier that never
densifies) and ``sq.digest`` (the job's last program): what a job pays to hold its
answer sparse and to close (ms)."""

from chipbench import sqscopes


def read(ctx):
    return sqscopes.scope_ms(ctx, ("sq.extract", "sq.digest"))
