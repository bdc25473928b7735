"""routing_ms (experts, ms): device time per step of the MoE layers'
routing, forward and backward, the operations under the model scope
``moe_route`` (``models/moe.py``): the router's gate and top-k, the
balance loss, the grouping of the routed rows by held expert and their
scatter back to their tokens; averaged over chips."""

import stages


def read(ctx):
    return stages.scope_ms(ctx, "moe_route")
