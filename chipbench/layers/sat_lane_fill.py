"""Scheduler / batcher: lane occupancy in the saturated cells, where it must
read 100: anything less means the closed loop did not keep a lane full."""

from chipbench.reading import lane_fill as read  # noqa: F401
