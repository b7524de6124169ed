"""Algorithms + local kernels: row pairs the harvest walked for every undirected edge
it counted (counter ``models.tc.pairs`` over ``models.tc.edges``; the program's own
counts).  About 2 while the scan walks every stored slot of the symmetric matrix and
weighs the upper triangle 0; 1 is a scan of the kept pairs alone."""

from chipbench.parts import counter


def read(ctx=None):
    pairs, edges = counter("models.tc.pairs"), counter("models.tc.edges")
    return pairs / edges if pairs is not None and edges else None
