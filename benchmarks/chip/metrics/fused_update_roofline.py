"""fused_update_roofline (kernels, %): the least time the chip could take
for the fused unpack + SGD kernel's work in one step, max(bytes / HBM
bandwidth, operations / bf16 peak), over its measured time per step. The
operations and bytes come from shapes (costs/fused_update.py: 17 bytes and
8 operations a parameter at 8 bits); the bytes bound it."""

import layers

KERNEL = "_unpack_sgd_kernel"


def read(ctx):
    ms = layers.ms_per_step(ctx, lambda c, n: KERNEL in (layers.kernel_of(c, n) or ""))
    if ms is None:
        return None
    c, p = ctx["cell"], ctx["peaks"]
    ops, nbytes = ctx["cost"]("fused_update")(c.config, c.traffic, c.chips)
    least = max(nbytes / p["hbm_bytes_per_s"], ops / p["bf16_flops_per_s"])
    return 100.0 * least / (ms * 1e-3)
