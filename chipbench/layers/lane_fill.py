"""Scheduler / batcher: ``srv.stats()["mean_occupancy"]`` as a percentage,
in the open-loop cells (partly empty lanes are the point there)."""

from chipbench.reading import lane_fill as read  # noqa: F401
