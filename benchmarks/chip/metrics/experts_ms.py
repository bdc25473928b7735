"""experts_ms (experts, ms): device time per step of the held experts'
grouped SwiGLU products over their routed rows, forward and backward, the
operations under the model scope ``moe_experts`` (``models/moe.py``, the
megablox grouped-matmul kernels and the SwiGLU between them); averaged
over chips."""

import stages


def read(ctx):
    return stages.scope_ms(ctx, "moe_experts")
