"""Multi-head Latent Attention (DeepSeek-V2) — low-rank KV compression.

Structure (per DeepSeek-V2 paper and its public ``modeling_deepseek.py``):
  c_kv = RMSNorm(x @ W_dkv)             (T, kv_lora)      shared latent
  k_c, v = c_kv @ W_uk, c_kv @ W_uv     per-head decompress (TP-sharded)
  k_rope = x @ W_kr                     (T, dh_rope)      shared rotary key
  q      = x @ W_q  (per head: content part + rotary part)
  softmax scale 1/sqrt(dh + dh_rope) · mscale², mscale from YaRN

Rotary positions may be YaRN-scaled (``rope_of``): the inverse frequencies
blend the original and the factor-divided ones over a ramp between the
correction dimensions of beta_fast and beta_slow, as
``DeepseekV2YarnRotaryEmbedding`` computes them. Each head's rotary part
rotates by halves (``common.rope``), a fixed permutation of the source's
interleaved layout.

Training attends causally through the Pallas flash attention kernel on
the chip (``_attend``): its grid skips the key blocks above the diagonal
and keeps no score matrix in HBM. The kernel needs one head width for q, k
and v, a multiple of 128, so q and k (192) and v (128) are zero-padded to
256, which leaves the scores and the values' columns unchanged. Off the chip
(CPU tests) the shared chunked online softmax computes the same attention.

The latent cache (c_kv + k_rope) is what decode stores — kv_lora(512) +
dh_rope(64) floats per token instead of 2·H·dh: the paper's KV-cache
compression. The latent projections are replicated (small); per-head
decompression matrices are column-parallel over TP.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu import flash_attention as flash

from repro.kernels import ops as kernel_ops
from repro.launch import stages
from repro.models.common import Axes, dense_init, rmsnorm, rope
from repro.models.attention import _chunked_attn, NEG_INF

DH_ROPE = 64
FLASH_BLOCK = 512  # query and key rows per block of the flash kernel
LANES = 128  # the flash kernel's head width is a multiple of this


def init_mla_params(
    key, d_model, n_heads_local, head_dim, kv_lora, dtype=jnp.float32
):
    ks = jax.random.split(key, 6)
    return {
        "w_dkv": dense_init(ks[0], (d_model, kv_lora), d_model, dtype),
        "kv_norm": jnp.ones((kv_lora,), dtype),
        "w_kr": dense_init(ks[1], (d_model, DH_ROPE), d_model, dtype),
        "w_uk": dense_init(ks[2], (kv_lora, n_heads_local * head_dim), kv_lora, dtype),
        "w_uv": dense_init(ks[3], (kv_lora, n_heads_local * head_dim), kv_lora, dtype),
        "w_q": dense_init(
            ks[4], (d_model, n_heads_local * (head_dim + DH_ROPE)), d_model, dtype
        ),
        "wo": dense_init(
            ks[5], (n_heads_local * head_dim, d_model), n_heads_local * head_dim, dtype
        ),
    }


# ---------------------------------------------------------------------------
# rotary positions: plain or YaRN
# ---------------------------------------------------------------------------
def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1·mscale·ln(scale) + 1 (1 if the
    context is not stretched)."""
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """The dim/2 inverse frequencies of YaRN: theta^(-2j/dim) below the
    correction dimension of beta_fast, divided by `factor` above that of
    beta_slow, and a linear blend between."""
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** exps
    inter = 1.0 / (factor * theta ** exps)

    def corr_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1: the original frequency, 0: the stretched one
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class MLARope:
    """The rotary part's frequencies and the scores' scale of one config."""

    inv_freq: Optional[tuple] = None  # None: theta^(-j/32)
    theta: float = 10000.0
    rot_scale: float = 1.0  # YaRN's mscale / mscale_all_dim on cos and sin
    score_scale: float = 1.0  # times 1/sqrt(dh + dh_rope)


def rope_of(cfg) -> MLARope:
    if not getattr(cfg, "yarn_factor", 0.0):
        return MLARope(theta=cfg.rope_theta)
    f = cfg.yarn_factor
    inv = yarn_inv_freq(DH_ROPE, cfg.rope_theta, f, cfg.yarn_original_max_position,
                        cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    m_all = yarn_mscale(f, cfg.yarn_mscale_all_dim) if cfg.yarn_mscale_all_dim else 1.0
    return MLARope(
        inv_freq=tuple(float(v) for v in inv),
        theta=cfg.rope_theta,
        rot_scale=yarn_mscale(f, cfg.yarn_mscale) / yarn_mscale(f, cfg.yarn_mscale_all_dim),
        score_scale=m_all * m_all,
    )


def _rotate(x, positions, r: MLARope):
    out = rope(x, positions, r.theta, r.inv_freq)
    if r.rot_scale != 1.0:
        out = (out.astype(jnp.float32) * r.rot_scale).astype(x.dtype)
    return out


def _split_q(q, n_heads, head_dim):
    q = q.reshape(q.shape[:-1] + (n_heads, head_dim + DH_ROPE))
    return q[..., :head_dim], q[..., head_dim:]


def _latent(params, x):
    c_kv = jnp.einsum("btd,dl->btl", x, params["w_dkv"].astype(x.dtype))
    return rmsnorm(c_kv, params["kv_norm"])


def mla_train(
    params, x, positions, axes: Axes, *, n_heads_local, head_dim,
    rot: MLARope = MLARope(),
):
    with stages.scope("mla"):
        b, t, _ = x.shape
        c_kv = _latent(params, x)
        k_r = jnp.einsum("btd,dr->btr", x, params["w_kr"].astype(x.dtype))
        k_r = _rotate(k_r.reshape(b, t, 1, DH_ROPE), positions, rot)
        k_c = jnp.einsum("btl,lk->btk", c_kv, params["w_uk"].astype(x.dtype))
        v = jnp.einsum("btl,lk->btk", c_kv, params["w_uv"].astype(x.dtype))
        q = jnp.einsum("btd,dk->btk", x, params["w_q"].astype(x.dtype))
        q_c, q_r = _split_q(q, n_heads_local, head_dim)
        q_r = _rotate(q_r, positions, rot)
        # concat content + rotary parts; K rotary part shared across heads
        q_full = jnp.concatenate([q_c, q_r], axis=-1)
        k_full = jnp.concatenate(
            [
                k_c.reshape(b, t, n_heads_local, head_dim),
                jnp.broadcast_to(k_r, (b, t, n_heads_local, DH_ROPE)),
            ],
            axis=-1,
        )
        v = v.reshape(b, t, n_heads_local, head_dim)
        out = _attend(q_full, k_full, v, positions, rot.score_scale)
        out = jnp.einsum(
            "btk,kd->btd",
            out.reshape(b, t, n_heads_local * head_dim),
            params["wo"].astype(x.dtype),
        )
        return axes.psum_tp(out)


def _attend(q, k, v, positions, score_scale: float):
    """Causal attention in sequence order: softmax(q·kᵀ · score_scale /
    sqrt(dq)) v. q, k: (B, T, H, dq); v: (B, T, H, dv) -> (B, T, H, dv).

    On the chip, the flash kernel, in blocks of ``FLASH_BLOCK`` rows (or
    T, if shorter), with every head zero-padded to one width, a multiple
    of ``LANES``. Off it the shared chunked online softmax, rematerialised,
    with v padded to dq and q scaled by `score_scale` (it scales by
    1/sqrt(dq) itself)."""
    t, dq, dv = q.shape[1], q.shape[-1], v.shape[-1]
    if kernel_ops._interpret_default():
        if score_scale != 1.0:
            q = (q.astype(jnp.float32) * score_scale).astype(q.dtype)
        vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dq - dv)))
        attn = jax.checkpoint(partial(_chunked_attn, window=None,
                                      chunk=min(1024, t)))
        return attn(q, k, vpad, positions, positions)[..., :dv]
    blk = min(FLASH_BLOCK, t)
    width = -(-max(dq, dv) // LANES) * LANES
    heads = lambda x: jnp.pad(jnp.swapaxes(x, 1, 2),
                              ((0, 0), (0, 0), (0, 0), (0, width - x.shape[-1])))
    sizes = flash.BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk,
        block_q_dkv=blk, block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)
    out = flash.flash_attention(
        heads(q), heads(k), heads(v), causal=True,
        sm_scale=score_scale / math.sqrt(dq), block_sizes=sizes)
    return jnp.swapaxes(out, 1, 2)[..., :dv]


def init_mla_cache(b_local, s_local, kv_lora, dtype=jnp.bfloat16):
    return {
        "c_kv": jnp.zeros((b_local, s_local, kv_lora), dtype),
        "k_r": jnp.zeros((b_local, s_local, DH_ROPE), dtype),
        "kv_pos": jnp.full((b_local, s_local), 2**30, jnp.int32),
    }


def mla_decode(params, x, pos, cache, axes: Axes, *, n_heads_local, head_dim,
               rot: MLARope = MLARope()):
    """One-token decode against the latent cache. x: (B,1,d)."""
    b = x.shape[0]
    c_new = _latent(params, x)[:, 0]
    k_r_new = jnp.einsum("btd,dr->btr", x, params["w_kr"].astype(x.dtype))
    k_r_new = _rotate(k_r_new.reshape(b, 1, 1, DH_ROPE), pos[:, None], rot)[:, 0, 0]

    s_loc = cache["c_kv"].shape[1]
    bidx = jnp.arange(b)
    slot = jnp.clip(pos, 0, s_loc - 1)
    c_cache = cache["c_kv"].at[bidx, slot].set(c_new.astype(cache["c_kv"].dtype))
    kr_cache = cache["k_r"].at[bidx, slot].set(k_r_new.astype(cache["k_r"].dtype))
    kv_pos = cache["kv_pos"].at[bidx, slot].set(pos)

    # decompress cached latents (the flop trade the MLA paper makes)
    k_c = jnp.einsum("bsl,lk->bsk", c_cache.astype(x.dtype), params["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsl,lk->bsk", c_cache.astype(x.dtype), params["w_uv"].astype(x.dtype))
    k_c = k_c.reshape(b, s_loc, n_heads_local, head_dim)
    v = v.reshape(b, s_loc, n_heads_local, head_dim)

    q = jnp.einsum("btd,dk->btk", x, params["w_q"].astype(x.dtype))
    q_c, q_r = _split_q(q, n_heads_local, head_dim)
    q_r = _rotate(q_r, pos[:, None], rot)
    scale = rot.score_scale / jnp.sqrt(jnp.asarray(head_dim + DH_ROPE, jnp.float32))
    logits = jnp.einsum(
        "bhd,bshd->bhs", q_c[:, 0].astype(jnp.float32), k_c.astype(jnp.float32)
    )
    logits += jnp.einsum(
        "bhr,bsr->bhs", q_r[:, 0].astype(jnp.float32), kr_cache.astype(jnp.float32)
    )
    logits *= scale
    mask = kv_pos[:, None, :] <= pos[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", w, v.astype(jnp.float32))
    out = out.reshape(b, 1, n_heads_local * head_dim).astype(x.dtype)
    out = jnp.einsum("btk,kd->btd", out, params["wo"].astype(x.dtype))
    return axes.psum_tp(out), dict(cache, c_kv=c_cache, k_r=kr_cache, kv_pos=kv_pos)
