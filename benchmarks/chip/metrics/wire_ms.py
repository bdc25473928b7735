"""wire_ms (wire, ms): device time per step of the stage ``wire``: the
codec's pack, the collective over the data-parallel axes and the unpack
(``CommCtx.psum_wire``), or the exact step's float mean; averaged over
chips."""

import stages


def read(ctx):
    return stages.ms(ctx, ("wire",))
