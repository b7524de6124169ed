"""Algorithms + local kernels: the busiest device's time a job under the scopes
``sq.extract`` (every window's dense accumulator back to tuples), ``sq.pack`` (the
tile's chunks counted and laid end to end under the fullest tile's count) and
``sq.digest`` (the sort by row, the running sums and the cross-tile ``psum`` /
``all_gather`` of the row vectors): what a chip pays to hand the product over (ms)."""

from chipbench import sqmscopes


def read(ctx):
    return sqmscopes.scope_ms(ctx, ("sq.extract", "sq.pack", "sq.digest"))
