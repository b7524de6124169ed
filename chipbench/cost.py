"""The table of peaks and the bytes a kernel must move.

Computed, not measured: every function here works from shapes alone and
says what it assumes.  A device that is not in ``peaks.json`` is an
error, never a default.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(have: {sorted(table)})"
        )
    return table[device_kind]


def ell_slots(E) -> int:
    """Padded nonzero slots of an ``EllParMat``: what a sweep over the
    matrix has to read whether a slot is real or padding."""
    return sum(int(bc.size) for bc, _, _ in E.buckets)


def bfs_batch_least_bytes(n: int, slots: int, width: int, levels: int,
                          ) -> int:
    """The least HBM traffic of one level-compressed batch BFS
    (``bfs_batch_compact``: one-byte level state per (vertex, root),
    parents rebuilt in one final pass) — a LOWER bound, so the share of
    the roofline it gives is an upper bound on how close the kernel is.

    Assumed: each of ``levels`` sweeps reads every padded column index
    once (4 B a slot), reads the ``[n, width]`` one-byte frontier once
    (a perfect cache: every gathered row is fetched once, not once per
    edge) and writes the ``[n, width]`` one-byte state once; the parents
    pass reads the indices and the levels once more and writes
    ``[n, width]`` int32 parents.  Nothing is charged for row ids,
    values, or the gather's real access pattern, which is what the
    measured time is expected to be dominated by."""
    per_level = 4 * slots + 2 * n * width
    parents = 4 * slots + n * width + 4 * n * width
    return levels * per_level + parents
