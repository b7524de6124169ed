"""The plain reference of GAP's CC kernel: the components of the
configuration's undirected graph by scipy's serial traversal, each
labelled with its smallest vertex id (numpy / scipy only; nothing from
the program).

GAP accepts any labelling that partitions the vertices as the serial
traversal does; the program states more (``combblas_tpu/models/cc.py``:
``labels[v]`` is the smallest id of ``v``'s component, an isolated
vertex labels itself), and the check holds it to what it states.  Labels
are integers, so where another kind's reference has a tolerance this one
has none: ``check_labels`` asks for ALL ``n`` entries equal, and nothing
computed in a lower precision could pass by rounding.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph


class CCReference:
    """``labels``: int32 ``[n]``, the smallest vertex id of each vertex's
    component; ``components``: how many there are; ``largest``: the
    vertices of the largest."""

    def __init__(self, n: int, rows, cols):
        self.n = int(n)
        adj = sp.csr_matrix(
            (np.ones(len(rows), np.int8), (rows, cols)), shape=(n, n)
        )
        self.components, comp = csgraph.connected_components(
            adj, directed=False
        )
        smallest = np.full(self.components, n, np.int64)
        np.minimum.at(smallest, comp, np.arange(n))
        self.labels = smallest[comp].astype(np.int32)
        self.largest = int(np.bincount(comp).max())

    def check_labels(self, labels) -> str | None:
        """None where ``labels`` equals the reference on every entry,
        else what is wrong: how many entries differ, the first of them,
        and the count of components and the size of the largest beside
        the reference's (a merged pair lowers the count, a split
        component raises it, the same partition under other names
        changes neither)."""
        labels = np.asarray(labels)
        if labels.shape != (self.n,) or labels.dtype.kind != "i":
            return (f"labels are {labels.dtype}{list(labels.shape)}, "
                    f"not one integer a vertex of {self.n}")
        differ = np.flatnonzero(labels != self.labels)
        if not len(differ):
            return None
        v = int(differ[0])
        sizes = np.unique(labels, return_counts=True)[1]
        bad = (f"{len(differ)} of {self.n} labels differ from the "
               f"reference: vertex {v} is labelled {int(labels[v])}, the "
               f"reference says {int(self.labels[v])}; {len(sizes)} "
               f"components where the reference has {self.components}, "
               f"the largest {int(sizes.max())} where it has "
               f"{self.largest}")
        pairs = np.unique(np.stack([labels, self.labels]), axis=1).shape[1]
        if pairs == len(sizes) == self.components:
            bad += ("; the partition is the reference's, the labels are "
                    "not each component's smallest vertex id")
        return bad
