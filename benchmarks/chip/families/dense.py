"""The dense decoder family: the plain reference model and its count of
operations.

A llama-style decoder: RMSNorm, rotary positions, grouped-query attention
(optionally over a sliding window), a SwiGLU feed-forward and an untied or
tied output head. ``init_params`` makes the weights from a PRNG key by the
recipe the configuration's model uses (uniform in ±1/sqrt(fan_in), ones for
the norm weights, one PRNG key per tensor split in the model's order);
``loss_fn`` is the mean next-token cross entropy with every matrix product
through ``mm`` (``reference.MATMULS``), so that the control computes the
same model at a lower precision. It imports nothing of the program.

``ops_per_token``: per token, the forward and backward passes take
6 x (parameters that enter a matrix product: the attention projections, the
feed-forward matrices and the output head; not the embedding lookup) plus,
per layer, attention's two matrix products over the keys each query sees:
12 x keys x heads x head_dim, with keys averaged over the positions under
the causal and window mask (about seq/2 when the window does not bind).
Recomputation under rematerialisation does not count.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512  # attention rows computed together; bounds the score tile
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------
def _uniform(key, shape, fan_in):
    s = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -s, s)


def _layer(key, c):
    d, f = c["d_model"], c["d_ff"]
    q, kv = c["n_heads"] * c["head_dim"], c["n_kv_heads"] * c["head_dim"]
    k_attn, k_mlp = jax.random.split(key, 2)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "ln2": jnp.ones((d,), jnp.float32),
        "attn": {
            "wq": _uniform(ka[0], (d, q), d),
            "wk": _uniform(ka[1], (d, kv), d),
            "wv": _uniform(ka[2], (d, kv), d),
            "wo": _uniform(ka[3], (q, d), q),
        },
        "mlp": {
            "w_gate": _uniform(km[0], (d, f), d),
            "w_up": _uniform(km[1], (d, f), d),
            "w_down": _uniform(km[2], (f, d), f),
        },
    }


def init_params(c, key):
    """The configuration's weights from a PRNG key, layers stacked."""
    d, v = c["d_model"], c["vocab"]
    keys = jax.random.split(key, 8)
    p = {
        "embed": _uniform(keys[0], (v, d), d),
        "ln_f": jnp.ones((d,), jnp.float32),
    }
    if not c["tie_embeddings"]:
        p["lm_head"] = _uniform(keys[1], (d, v), d)
    layers = [_layer(k, c) for k in jax.random.split(keys[2], c["n_layers"])]
    p["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return p


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _rmsnorm(x, w, eps=1e-6):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate the two halves of each head (x: B, T, H, dh; pos: T)."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(p, x, c, mm):
    b, t, _ = x.shape
    nq, nkv, dh = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    group = nq // nkv
    pos = jnp.arange(t, dtype=jnp.int32)
    q = _rope(mm(x, p["wq"]).reshape(b, t, nq, dh), pos, c["rope_theta"])
    k = _rope(mm(x, p["wk"]).reshape(b, t, nkv, dh), pos, c["rope_theta"])
    v = mm(x, p["wv"]).reshape(b, t, nkv, dh)
    # query head h reads key/value head h // group
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    window = c.get("window")

    @jax.checkpoint
    def rows(qb, qpos):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST)
        s = s / math.sqrt(dh)
        ok = pos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= pos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)

    blk = min(QUERY_BLOCK, t)
    out = jnp.concatenate(
        [rows(q[:, i:i + blk], pos[i:i + blk]) for i in range(0, t, blk)],
        axis=1,
    )
    return mm(out.reshape(b, t, nq * dh), p["wo"])


def _mlp(p, x, mm):
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])


def loss_fn(params, tokens, labels, c, mm):
    """Mean next-token cross entropy over the positions whose label >= 0."""
    x = params["embed"][tokens]
    for i in range(c["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])

        @jax.checkpoint
        def layer(x, lp):
            h = x + _attention(lp["attn"], _rmsnorm(x, lp["ln1"]), c, mm)
            return h + _mlp(lp["mlp"], _rmsnorm(h, lp["ln2"]), mm)

        x = layer(x, lp)
    h = _rmsnorm(x, params["ln_f"])
    head = params["embed"].T if c["tie_embeddings"] else params["lm_head"]
    logits = mm(h, head)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.clip(labels, 0, None)[..., None], axis=-1
    )[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# operations a training step requires
# ---------------------------------------------------------------------------
def matmul_params(c: dict) -> int:
    d, dh = c["d_model"], c["head_dim"]
    q, kv = c["n_heads"] * dh, c["n_kv_heads"] * dh
    layer = d * q + 2 * d * kv + q * d + 3 * d * c["d_ff"]
    return c["n_layers"] * layer + d * c["vocab"]


def mean_keys(seq: int, window) -> float:
    w = window or seq
    return sum(min(p + 1, w) for p in range(seq)) / seq


def ops_per_token(c: dict, seq: int) -> float:
    """Operations of the forward and backward passes per token."""
    attn = (12 * mean_keys(seq, c["window"]) * c["n_heads"]
            * c["head_dim"] * c["n_layers"])
    return 6 * matmul_params(c) + attn
