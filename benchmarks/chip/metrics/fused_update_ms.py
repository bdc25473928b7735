"""fused_update_ms (kernels, ms): device time of the fused unpack +
momentum-SGD Pallas kernel (kernels/fused_update.py) per step, summed over
its calls (one per parameter leaf), averaged over chips."""

import layers

KERNEL = "_unpack_sgd_kernel"


def is_kernel(ctx, name):
    return KERNEL in (layers.kernel_of(ctx, name) or "")


def read(ctx):
    return layers.ms_per_step(ctx, is_kernel)
