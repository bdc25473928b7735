"""Operations one training step of a dense decoder requires.

Per token, the forward and backward passes take 6 x (parameters that enter
a matrix product: the attention projections, the feed-forward matrices and
the output head; not the embedding lookup) plus, per layer, attention's two
matrix products over the keys each query sees: 12 x keys x heads x
head_dim, with keys averaged over the positions under the causal and window
mask (about seq/2 when the window does not bind). Recomputation under
rematerialisation does not count. Returns (operations, bytes) per step; the
step's bytes are not modelled (None).
"""


def matmul_params(c: dict) -> int:
    d, dh = c["d_model"], c["head_dim"]
    q, kv = c["n_heads"] * dh, c["n_kv_heads"] * dh
    layer = d * q + 2 * d * kv + q * d + 3 * d * c["d_ff"]
    return c["n_layers"] * layer + d * c["vocab"]


def mean_keys(seq: int, window) -> float:
    w = window or seq
    return sum(min(p + 1, w) for p in range(seq)) / seq


def cost(config: dict, traffic: dict, chips: int):
    c, t = config, traffic
    tokens = t["batch_per_chip"] * chips * t["seq_len"]
    attn = (12 * mean_keys(t["seq_len"], c["window"]) * c["n_heads"]
            * c["head_dim"] * c["n_layers"])
    return tokens * (6 * matmul_params(c) + attn), None
