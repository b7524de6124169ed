"""Scheduler / batcher: from the end of one batch's scatter pass to the worker's
pop of the next batch, on the one monotonic clock of the stage records, median
(ms): what the serial worker spends between batches while the device idles."""

from chipbench.parts import batch_gap_ms as read  # noqa: F401
