"""Algorithms + local kernels: per batch, the part ``device`` of its ``execute``
(host clock around ``block_until_ready``) over the batch's own gathered ``slots``;
median over the window's batches (ns a slot).  The traced run logs the same by lane
width: count, median wave, median Mslots, ns a slot."""

from chipbench.ellwork import wave_ns_per_slot as read  # noqa: F401
