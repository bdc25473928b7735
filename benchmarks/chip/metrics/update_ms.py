"""update_ms (optimizer, ms): device time per step from the summed
integers to the new weights, the stages ``decode``, ``clip`` and
``update``: the decode, the global-norm clip, and the ZeRO-1 update with
its all-gather or the fused route with its pads, reshapes and Pallas
kernel; averaged over chips."""

import stages


def read(ctx):
    return stages.ms(ctx, ("decode", "clip", "update"))
