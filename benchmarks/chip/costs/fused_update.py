"""What the fused unpack + momentum-SGD kernel (kernels/fused_update.py,
``_unpack_sgd_kernel``) must move and compute in one training step.

It runs once per parameter leaf, over every parameter on the chip. Per
parameter it reads its field of a packed transport word (bits/8 bytes),
reads and writes the f32 parameter and the f32 momentum (16 bytes), and
computes 8 operations: the decode's two scalings, weight decay, the
momentum update and the step, two each for the last three. Returns
(operations, bytes) per step.
"""


def parameters(c: dict) -> int:
    d, dh = c["d_model"], c["head_dim"]
    q, kv = c["n_heads"] * dh, c["n_kv_heads"] * dh
    layer = 2 * d + d * q + 2 * d * kv + q * d + 3 * d * c["d_ff"]
    head = 0 if c["tie_embeddings"] else d * c["vocab"]
    return c["n_layers"] * layer + c["vocab"] * d + d + head


def cost(config: dict, traffic: dict, chips: int):
    n = parameters(config)
    return 8 * n, n * (traffic["bits"] / 8 + 16)
