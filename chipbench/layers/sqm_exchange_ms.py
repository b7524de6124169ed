"""Distributed ops: the busiest device's time a job under the scope ``sq.exchange``: the
numeric phase's stage exchange of operand tiles (the ``all_gather`` of A's tiles along
the grid row and of B's along the grid column; the ``ppermute`` rotations where the
carousel runs), the waits for the other chips included (ms)."""

from chipbench import sqmscopes


def read(ctx):
    return sqmscopes.scope_ms(ctx, ("sq.exchange",))
