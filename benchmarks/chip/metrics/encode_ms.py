"""encode_ms (compressor, ms): device time per step of the stage
``encode``, the stochastic rounding of α·g and its clip to the integer
range (``IntSGD.encode_ints``), averaged over chips. Where XLA fuses the
rounding into the wire's pack, that time counts for ``wire``."""

import stages


def read(ctx):
    return stages.ms(ctx, ("encode",))
