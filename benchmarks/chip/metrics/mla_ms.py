"""mla_ms (attention, ms): device time per step of multi-head latent
attention, forward and backward, the operations under the model scope
``mla`` (``models/mla.py::mla_train``) inside ``fwd_bwd``, loop bodies
included; averaged over chips."""

import stages


def read(ctx):
    return stages.scope_ms(ctx, "mla")
