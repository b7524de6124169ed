"""Algorithms + local kernels: steps of a job taken as a walk of the lists that are
live over all its steps (%): a round's proposals and its free degrees
(``models.mcm.init_steps{mode}``) and a phase's layers (``models.mcm.layers{mode}``);
every other step swept the whole matrix."""

from chipbench.mcmwork import push_share as read  # noqa: F401
