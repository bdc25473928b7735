"""experts_roofline (experts, %): the least time the chip could take for
the held experts' grouped products in one step, max(bytes / HBM
bandwidth, operations / bf16 peak), over ``experts_ms``. The operations
and bytes come from shapes (costs/moe_experts.py: 18 d f operations per
expected routed row, forward and backward, recomputation not counted);
the operations bound it."""

import stages


def read(ctx):
    ms = stages.scope_ms(ctx, "moe_experts")
    if not ms:
        return None
    c, p = ctx["cell"], ctx["peaks"]
    ops, nbytes = ctx["cost"]("moe_experts")(c.config, c.traffic, c.chips)
    least = max(nbytes / p["hbm_bytes_per_s"], ops / p["bf16_flops_per_s"])
    return 100.0 * least / (ms * 1e-3)
