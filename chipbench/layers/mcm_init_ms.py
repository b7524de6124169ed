"""Algorithms + local kernels: device time of a job under ``mcm.init``, the Karp-Sipser
rounds that build the maximal matching the phases start from (a round: the proposers'
lists walked or the matrix swept, a scatter and a subscript of one side's vector, the
just matched columns' lists walked or swept), per whole execution (ms)."""


def read(ctx):
    from chipbench.mcmscopes import under_ms

    return under_ms(ctx, "mcm.init")
