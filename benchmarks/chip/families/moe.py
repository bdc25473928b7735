"""The MoE family: DeepSeek-V2's decoder at one chip's share of an
expert-parallel deployment, as a plain reference model, and its count of
operations.

Layers: the leading dense layers (``first_k_dense``, SwiGLU of width
``d_ff_dense``), then the MoE layers. Every layer's attention is
multi-head latent attention where ``kv_lora`` is set (else the dense
family's grouped-query attention):

  c = RMSNorm(x W_dkv)                    the latent, kv_lora wide
  k_h = [c W_uk,h ; rope(x W_kr)]         content key and a rotary key
                                          shared by every head
  v_h = c W_uv,h ;  q_h = x W_q,h = [q_c ; rope(q_r)]
  scores q_h·k_h · mscale² / sqrt(dh + dh_rope), causal softmax

with YaRN's rotary frequencies and temperature (``yarn_*``: the source's
``rope_scaling``), as ``DeepseekV2YarnRotaryEmbedding`` computes them. An
MoE layer routes each token by a softmax over all ``n_experts`` and its
top ``top_k`` (weights renormalised over the k only if
``norm_topk_prob``), and adds ``n_experts_held`` experts' part: written as
a masked sum, every held expert on every token times the token's gate
weight for that expert if it is among the token's top k, else 0, so it
has no grouping of its own and drops nothing. Then the shared experts, one
SwiGLU of width ``n_shared_experts`` x ``d_ff``. The loss adds, per MoE
layer, the sequence-wise balance loss: ``aux_loss_alpha`` x the mean over
sequences of sum_i f_i P_i, f_i the expert's share of the sequence's
choices times E / k (a count, no gradient) and P_i its mean probability.

In f32, every product with a weight through ``mm`` (``reference.MATMULS``),
the attention products at ``highest``. It imports nothing of the program.
RMSNorm and the SwiGLU are the dense family's. ``init_params`` follows the
program's keys: per MoE layer one key each for the attention, router,
experts' gate, up and down projections, and the shared experts.

Departures from the source, each also in the configuration's ``assumed``:
- each head's rotary part rotates by halves, where the source interleaves
  its pairs: a fixed permutation of the rotary columns of W_q and W_kr,
  exact under random weights;
- ``aux_loss_alpha`` 0.001 is the source config.json's, recalled: the
  catalog leaves the key out.

``ops_per_token``: 6 x the weights a token meets on this chip in a matrix
product (attention, the dense layers' SwiGLU, the router, the shared
experts, the head, and top_k x held / n_experts of one routed expert per
MoE layer), plus per layer attention's two products over the keys a query
sees under the causal mask: 6 x keys x heads x (dh + dh_rope + dh).
Recomputation does not count.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import bench

_dense = bench.family("dense")
_rmsnorm, _mlp, _uniform = _dense._rmsnorm, _dense._mlp, _dense._uniform

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = _dense.QUERY_BLOCK
NEG_INF = _dense.NEG_INF
DH_ROPE = 64  # qk_rope_head_dim


def n_held(c) -> int:
    return c.get("n_experts_held") or c["n_experts"]


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------
def _swiglu(key, d, f):
    k = jax.random.split(key, 3)
    return {"w_gate": _uniform(k[0], (d, f), d),
            "w_up": _uniform(k[1], (d, f), d),
            "w_down": _uniform(k[2], (f, d), f)}


def _attn(key, c):
    d, lat, h, dh = c["d_model"], c["kv_lora"], c["n_heads"], c["head_dim"]
    if not lat:  # the dense family's grouped-query attention
        q, kv = h * dh, c["n_kv_heads"] * dh
        k = jax.random.split(key, 4)
        return {"wq": _uniform(k[0], (d, q), d), "wk": _uniform(k[1], (d, kv), d),
                "wv": _uniform(k[2], (d, kv), d), "wo": _uniform(k[3], (q, d), q)}
    k = jax.random.split(key, 6)
    return {
        "w_dkv": _uniform(k[0], (d, lat), d),
        "kv_norm": jnp.ones((lat,), jnp.float32),
        "w_kr": _uniform(k[1], (d, DH_ROPE), d),
        "w_uk": _uniform(k[2], (lat, h * dh), lat),
        "w_uv": _uniform(k[3], (lat, h * dh), lat),
        "w_q": _uniform(k[4], (d, h * (dh + DH_ROPE)), d),
        "wo": _uniform(k[5], (h * dh, d), h * dh),
    }


def _dense_layer(key, c):
    d = c["d_model"]
    k_attn, k_mlp = jax.random.split(key, 2)
    return {"ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "attn": _attn(k_attn, c),
            "mlp": _swiglu(k_mlp, d, c["d_ff_dense"])}


def _moe_layer(key, c):
    d, f, e = c["d_model"], c["d_ff"], n_held(c)
    k_attn, k_router, k_gate, k_up, k_down, k_shared = jax.random.split(key, 6)
    moe = {
        "router": _uniform(k_router, (d, c["n_experts"]), d),
        "w_gate": _uniform(k_gate, (e, d, f), d),
        "w_up": _uniform(k_up, (e, d, f), d),
        "w_down": _uniform(k_down, (e, f, d), f),
    }
    if c["n_shared_experts"]:
        moe["shared"] = _swiglu(k_shared, d, c["n_shared_experts"] * f)
    return {"ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "attn": _attn(k_attn, c), "moe": moe}


def _stack(layers):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def init_params(c, key):
    """The configuration's weights from a PRNG key: the MoE layers stacked
    under ``layers``, the leading dense layers under ``dense_layers``."""
    d, v = c["d_model"], c["vocab"]
    keys = jax.random.split(key, 8)
    p = {"embed": _uniform(keys[0], (v, d), d),
         "ln_f": jnp.ones((d,), jnp.float32)}
    if not c["tie_embeddings"]:
        p["lm_head"] = _uniform(keys[1], (d, v), d)
    n_moe = c["n_layers"] - c["first_k_dense"]
    p["layers"] = _stack([_moe_layer(k, c)
                          for k in jax.random.split(keys[2], n_moe)])
    if c["first_k_dense"]:
        p["dense_layers"] = _stack([
            _dense_layer(k, c)
            for k in jax.random.split(keys[3], c["first_k_dense"])])
    return p


# ---------------------------------------------------------------------------
# rotary positions: YaRN
# ---------------------------------------------------------------------------
def _yarn_mscale(scale, mscale):
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def rotary(c):
    """(inverse frequencies (DH_ROPE/2,), scale of cos and sin, scale of
    the scores beyond 1/sqrt(dh + dh_rope))."""
    dim, base, f = DH_ROPE, c["rope_theta"], c.get("yarn_factor") or 0.0
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    plain = 1.0 / base ** exps
    if not f:
        return plain, 1.0, 1.0

    def corr_dim(rot):
        return (dim * math.log(c["yarn_original_max_position"]
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(corr_dim(c["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr_dim(c["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = plain / f * ramp + plain * (1.0 - ramp)
    m, m_all = c["yarn_mscale"], c["yarn_mscale_all_dim"]
    score = _yarn_mscale(f, m_all) ** 2 if m_all else 1.0
    return inv, _yarn_mscale(f, m) / _yarn_mscale(f, m_all), score


def _rotate(x, pos, inv_freq, scale):
    """Rotate the two halves of each head (x: B, T, H, DH_ROPE)."""
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1) * scale


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _mla(p, x, c, mm):
    b, t, _ = x.shape
    h, dh = c["n_heads"], c["head_dim"]
    inv, rot_scale, score = rotary(c)
    pos = jnp.arange(t, dtype=jnp.int32)
    lat = _rmsnorm(mm(x, p["w_dkv"]), p["kv_norm"])
    kc = mm(lat, p["w_uk"]).reshape(b, t, h, dh)
    v = mm(lat, p["w_uv"]).reshape(b, t, h, dh)
    kr = _rotate(mm(x, p["w_kr"]).reshape(b, t, 1, DH_ROPE), pos, inv,
                 rot_scale)[:, :, 0]
    q = mm(x, p["w_q"]).reshape(b, t, h, dh + DH_ROPE)
    qc = q[..., :dh]
    qr = _rotate(q[..., dh:], pos, inv, rot_scale)
    scale = score / math.sqrt(dh + DH_ROPE)

    @jax.checkpoint
    def rows(blocks):
        qc_b, qr_b, qpos = blocks
        s = (jnp.einsum("bqhd,bkhd->bhqk", qc_b, kc, precision=HIGHEST)
             + jnp.einsum("bqhr,bkr->bhqk", qr_b, kr, precision=HIGHEST))
        s = jnp.where((pos[None, :] <= qpos[:, None])[None, None],
                      s * scale, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)

    # query blocks, each against every key under the causal mask
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    split = lambda a, ax: jnp.moveaxis(
        a.reshape(a.shape[:ax] + (t // blk, blk) + a.shape[ax + 1:]), ax, 0)
    out = lax.map(rows, (split(qc, 1), split(qr, 1), split(pos, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, h, dh)
    return mm(out.reshape(b, t, h * dh), p["wo"])


def _attention(p, x, c, mm):
    return _mla(p, x, c, mm) if c["kv_lora"] else _dense._attention(p, x, c, mm)


def gates(p, xf, c, mm):
    """(probs (N, E), the top-k gate weights (N, k), their experts (N, k))."""
    probs = jax.nn.softmax(mm(xf, p["router"]), axis=-1)
    w, ids = lax.top_k(probs, c["top_k"])
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return probs, w, ids


def balance(probs, ids, n_seq, c):
    """The sequence-wise balance loss, times ``aux_loss_alpha``."""
    e, k = c["n_experts"], c["top_k"]
    t = probs.shape[0] // n_seq
    count = jnp.sum(jax.nn.one_hot(ids, e), axis=1).reshape(n_seq, t, e)
    f = lax.stop_gradient(jnp.sum(count, axis=1) * (e / (t * k)))
    prob = jnp.mean(probs.reshape(n_seq, t, e), axis=1)
    return c["aux_loss_alpha"] * jnp.mean(jnp.sum(f * prob, axis=-1))


def routed(p, xf, w, ids, mm, first=0):
    """The held experts' part: every held expert on every token, times the
    token's gate weight for it (0 where it is not among the token's top
    k), summed over the held experts, which are first, first + 1, ... of
    the router's."""

    @jax.checkpoint
    def one(out, expert):
        e, wg, wu, wd = expert
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        y = _mlp({"w_gate": wg, "w_up": wu, "w_down": wd}, xf, mm)
        return out + gate[:, None] * y, None

    held = first + jnp.arange(p["w_gate"].shape[0])
    out, _ = lax.scan(one, jnp.zeros_like(xf),
                      (held, p["w_gate"], p["w_up"], p["w_down"]))
    return out


def moe(p, x, c, mm):
    """(the MoE block's output, its balance loss)."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    probs, w, ids = gates(p, xf, c, mm)
    out = routed(p, xf, w, ids, mm)
    if "shared" in p:
        out = out + _mlp(p["shared"], xf, mm)
    aux = balance(probs, ids, b, c) if c["aux_loss_alpha"] else 0.0
    return out.reshape(b, t, d), aux


def loss_fn(params, tokens, labels, c, mm):
    """Mean next-token cross entropy over the positions whose label >= 0,
    plus every MoE layer's balance loss."""
    x = params["embed"][tokens]

    @jax.checkpoint
    def dense_layer(x, lp):
        h = x + _attention(lp["attn"], _rmsnorm(x, lp["ln1"]), c, mm)
        return h + _mlp(lp["mlp"], _rmsnorm(h, lp["ln2"]), mm)

    @jax.checkpoint
    def moe_layer(x, lp):
        h = x + _attention(lp["attn"], _rmsnorm(x, lp["ln1"]), c, mm)
        out, aux = moe(lp["moe"], _rmsnorm(h, lp["ln2"]), c, mm)
        return h + out, jnp.asarray(aux, jnp.float32)

    for i in range(c["first_k_dense"]):
        x = dense_layer(x, jax.tree.map(lambda a: a[i], params["dense_layers"]))
    x, aux = lax.scan(moe_layer, x, params["layers"])
    h = _rmsnorm(x, params["ln_f"])
    head = params["embed"].T if c["tie_embeddings"] else params["lm_head"]
    logits = mm(h, head)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.clip(labels, 0, None)[..., None], axis=-1
    )[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = jnp.sum((logz - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return ce + jnp.sum(aux)


# ---------------------------------------------------------------------------
# operations a training step requires
# ---------------------------------------------------------------------------
def attn_params(c: dict) -> int:
    d, h, dh, lat = c["d_model"], c["n_heads"], c["head_dim"], c["kv_lora"]
    if not lat:
        q, kv = h * dh, c["n_kv_heads"] * dh
        return d * q + 2 * d * kv + q * d
    return (d * lat + d * DH_ROPE + 2 * lat * h * dh
            + d * h * (dh + DH_ROPE) + h * dh * d)


def matmul_params(c: dict) -> float:
    """Weights a token meets in a matrix product on this chip, with a
    routed expert counted at the share of the tokens it sees,
    top_k x held / n_experts."""
    d, f = c["d_model"], c["d_ff"]
    n_moe = c["n_layers"] - c["first_k_dense"]
    expert = 3 * d * f
    moe = (d * c["n_experts"] + c["n_shared_experts"] * expert
           + c["top_k"] * n_held(c) / c["n_experts"] * expert)
    return (c["n_layers"] * attn_params(c)
            + c["first_k_dense"] * 3 * d * c["d_ff_dense"]
            + n_moe * moe + d * c["vocab"])


def ops_per_token(c: dict, seq: int) -> float:
    """Operations of the forward and backward passes per token."""
    keys = _dense.mean_keys(seq, c.get("window"))
    width = (2 * c["head_dim"] + DH_ROPE) if c["kv_lora"] else 2 * c["head_dim"]
    return 6 * matmul_params(c) + 6 * keys * c["n_heads"] * width * c["n_layers"]
