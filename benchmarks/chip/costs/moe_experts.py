"""What the held experts' grouped SwiGLU products (models/moe.py,
``moe_experts``) must compute and move in one training step.

Per MoE layer the chip's held experts see, in expectation, rows = tokens x
top_k x held / n_experts (token, choice) pairs. Each row goes through three
products of d x f (gate, up, down): 6 d f operations forward and 12 d f
backward (the data and the weight gradients), 18 d f in all;
recomputation under rematerialisation does not count. Bytes, in bfloat16
as the products run: the held experts' weights read in the forward and
again in the backward, their weight gradients written, and the rows'
activations in and out of each product (x in, gate and up out; their
SwiGLU in, y out) in the forward and twice in the backward. Returns
(operations, bytes) per step.
"""


def cost(config: dict, traffic: dict, chips: int):
    c, t = config, traffic
    d, f = c["d_model"], c["d_ff"]
    held = c.get("n_experts_held") or c["n_experts"]
    n_moe = c["n_layers"] - c.get("first_k_dense", 0)
    tokens = t["batch_per_chip"] * chips * t["seq_len"]
    rows = tokens * c["top_k"] * held / c["n_experts"]
    ops = 18 * d * f * rows * n_moe
    weights = 3 * held * d * f * 2  # bf16
    acts = rows * (d + 2 * f + f + d) * 2
    return ops, n_moe * (3 * weights + 3 * acts)
