"""Decoder-only LM assembly covering the dense / MoE / MLA / hybrid-SSM /
xLSTM / VLM families, with scan-over-layers and TP-aware modules.

Parameter creation is parameterized by ``n_shards`` ∈ {1, tp}: with
n_shards=1 you get the GLOBAL (padded-for-tp) shapes, with n_shards=tp the
LOCAL per-device shard shapes. launch/specs.py derives PartitionSpecs by
diffing the two shape trees — no hand-maintained sharding table can drift
out of sync with the model code.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import (
    Axes,
    HeadLayout,
    dense_init,
    embed_lookup,
    pad_to_multiple,
    plan_heads,
    rmsnorm,
    tp_cross_entropy,
)
from repro.models.mlp import init_swiglu, swiglu_mlp


@dataclasses.dataclass(frozen=True)
class Dims:
    """All local tensor dims for a given (cfg, tp, n_shards)."""

    layout: HeadLayout
    d_ff_loc: int
    vocab_loc: int
    # moe
    e_loc: int = 0
    ff_e_loc: int = 0
    ff_shared_loc: int = 0
    ff_dense_loc: int = 0  # the leading dense layers' SwiGLU
    # ssm
    ssm_heads_loc: int = 0
    ssm_head_dim: int = 64
    # xlstm
    xl_heads_loc: int = 0
    xl_head_dim: int = 0


def resolve_dims(cfg, tp: int, n_shards: int) -> Dims:
    head_dim = cfg.head_dim or cfg.d_model // cfg.n_heads
    layout_g = plan_heads(cfg.n_heads, cfg.n_kv_heads, head_dim, tp)
    layout = HeadLayout(
        layout_g.n_q,
        layout_g.n_kv,
        head_dim,
        layout_g.n_q // n_shards,
        layout_g.n_kv // n_shards,
    )
    d_ff_pad = pad_to_multiple(max(cfg.d_ff, tp), tp)
    vocab_pad = pad_to_multiple(cfg.vocab, tp)
    kw = {}
    if cfg.n_experts:
        strategy = moe_mod.pick_strategy(cfg.n_experts, tp)
        if strategy == "held":
            kw["e_loc"] = cfg.n_experts_held or cfg.n_experts
            kw["ff_e_loc"] = cfg.d_ff
        elif strategy == "ep":
            kw["e_loc"] = cfg.n_experts // n_shards
            kw["ff_e_loc"] = cfg.d_ff
        else:
            kw["e_loc"] = cfg.n_experts
            kw["ff_e_loc"] = pad_to_multiple(cfg.d_ff, tp) // n_shards
        if cfg.n_shared_experts:
            ff_sh = pad_to_multiple(cfg.d_ff * cfg.n_shared_experts, tp)
            kw["ff_shared_loc"] = ff_sh // n_shards
        if cfg.first_k_dense:
            kw["ff_dense_loc"] = pad_to_multiple(cfg.d_ff_dense, tp) // n_shards
    if cfg.ssm_state:
        d_inner = 2 * cfg.d_model
        heads = d_inner // 64
        kw["ssm_heads_loc"] = pad_to_multiple(heads, tp) // n_shards
        kw["ssm_head_dim"] = 64
    if cfg.family == "ssm":  # xlstm
        kw["xl_heads_loc"] = pad_to_multiple(cfg.n_heads, tp) // n_shards
        kw["xl_head_dim"] = head_dim
    return Dims(
        layout=layout,
        d_ff_loc=d_ff_pad // n_shards,
        vocab_loc=vocab_pad // n_shards,
        **kw,
    )


# ---------------------------------------------------------------------------
# per-layer init/apply
# ---------------------------------------------------------------------------
def _init_dense_layer(key, cfg, dims: Dims, dtype, d_ff_loc=None):
    ks = jax.random.split(key, 2)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "mlp": init_swiglu(ks[1], cfg.d_model, d_ff_loc or dims.d_ff_loc, dtype),
    }
    if cfg.kv_lora:
        p["attn"] = mla_mod.init_mla_params(
            ks[0], cfg.d_model, dims.layout.q_local, dims.layout.head_dim, cfg.kv_lora, dtype
        )
    else:
        p["attn"] = attn.init_attn_params(
            ks[0], cfg.d_model, dims.layout, bias=cfg.qkv_bias, dtype=dtype
        )
    return p


def _init_moe_layer(key, cfg, dims: Dims, dtype):
    """One key per tensor: attention, router, the experts' gate, up and
    down projections, the shared experts."""
    k_attn, k_router, k_gate, k_up, k_down, k_shared = jax.random.split(key, 6)
    d, e, f = cfg.d_model, dims.e_loc, dims.ff_e_loc
    p = {
        "ln1": jnp.ones((d,), dtype),
        "ln2": jnp.ones((d,), dtype),
        "moe": {
            "router": dense_init(k_router, (d, cfg.n_experts), d, jnp.float32),
            "w_gate": dense_init(k_gate, (e, d, f), d, dtype),
            "w_up": dense_init(k_up, (e, d, f), d, dtype),
            "w_down": dense_init(k_down, (e, f, d), f, dtype),
        },
    }
    if cfg.n_shared_experts:
        p["moe"]["shared"] = init_swiglu(k_shared, d, dims.ff_shared_loc, dtype)
    if cfg.kv_lora:
        p["attn"] = mla_mod.init_mla_params(
            k_attn, d, dims.layout.q_local, dims.layout.head_dim, cfg.kv_lora, dtype
        )
    else:
        p["attn"] = attn.init_attn_params(
            k_attn, d, dims.layout, bias=cfg.qkv_bias, dtype=dtype
        )
    return p


def _apply_attn_train(p, x, positions, axes, cfg, dims):
    if cfg.kv_lora:
        return mla_mod.mla_train(
            p, x, positions, axes,
            n_heads_local=dims.layout.q_local, head_dim=dims.layout.head_dim,
            rot=mla_mod.rope_of(cfg),
        )
    return attn.attention_train(
        p, x, positions, axes, dims.layout,
        window=cfg.window, rope_theta=cfg.rope_theta,
    )


def _dense_layer(p, x, positions, axes, cfg, dims):
    h = x + _apply_attn_train(p["attn"], rmsnorm(x, p["ln1"]), positions, axes, cfg, dims)
    h = h + swiglu_mlp(p["mlp"], rmsnorm(h, p["ln2"]), axes)
    return h


def _moe_layer(p, x, positions, axes, cfg, dims):
    """Returns (h, (aux loss, rows per held expert or None))."""
    h = x + _apply_attn_train(p["attn"], rmsnorm(x, p["ln1"]), positions, axes, cfg, dims)
    out, aux, rows = moe_mod.moe_block(p["moe"], rmsnorm(h, p["ln2"]), axes, cfg)
    return h + out, (aux, rows)


# ---- zamba2-style hybrid: mamba backbone + shared attention block ----------
def _init_mamba_layer(key, cfg, dims: Dims, dtype):
    return {
        "ln": jnp.ones((cfg.d_model,), dtype),
        "m": ssm_mod.init_mamba2_params(
            key, cfg.d_model, dims.ssm_heads_loc, dims.ssm_head_dim, cfg.ssm_state, dtype
        ),
    }


def _init_shared_attn(key, cfg, dims: Dims, dtype):
    ks = jax.random.split(key, 4)
    return {
        "ln": jnp.ones((2 * cfg.d_model,), dtype),
        "w_in": dense_init(ks[0], (2 * cfg.d_model, cfg.d_model), 2 * cfg.d_model, dtype),
        "attn": attn.init_attn_params(ks[1], cfg.d_model, dims.layout, dtype=dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "mlp": init_swiglu(ks[2], cfg.d_model, dims.d_ff_loc, dtype),
    }


def _shared_attn_block(p, h, emb, positions, axes, cfg, dims):
    z = jnp.concatenate([h, emb], axis=-1)
    z = rmsnorm(z, p["ln"])
    z = jnp.einsum("btd,dk->btk", z, p["w_in"].astype(z.dtype))
    z = z + attn.attention_train(
        p["attn"], z, positions, axes, dims.layout, rope_theta=cfg.rope_theta
    )
    z = z + swiglu_mlp(p["mlp"], rmsnorm(z, p["ln2"]), axes)
    return h + z


# ---- xlstm blocks -----------------------------------------------------------
def _init_xlstm_block(key, cfg, dims: Dims, dtype):
    """One (mLSTM, mLSTM, sLSTM) block."""
    ks = jax.random.split(key, 3)
    mk = lambda k: {
        "ln": jnp.ones((cfg.d_model,), dtype),
        "cell": xlstm_mod.init_mlstm_params(
            k, cfg.d_model, dims.xl_heads_loc, dims.xl_head_dim, dtype
        ),
    }
    return {
        "m1": mk(ks[0]),
        "m2": mk(ks[1]),
        "s": {
            "ln": jnp.ones((cfg.d_model,), dtype),
            "cell": xlstm_mod.init_slstm_params(
                ks[2], cfg.d_model, dims.xl_heads_loc, dims.xl_head_dim, dtype
            ),
        },
    }


def _xlstm_block(p, x, axes, cfg, dims):
    kw = dict(n_heads_local=dims.xl_heads_loc, head_dim=dims.xl_head_dim)
    x = x + xlstm_mod.mlstm_train(p["m1"]["cell"], rmsnorm(x, p["m1"]["ln"]), axes, **kw)
    x = x + xlstm_mod.mlstm_train(p["m2"]["cell"], rmsnorm(x, p["m2"]["ln"]), axes, **kw)
    x = x + xlstm_mod.slstm_train(p["s"]["cell"], rmsnorm(x, p["s"]["ln"]), axes, **kw)
    return x


# ---------------------------------------------------------------------------
# full model init
# ---------------------------------------------------------------------------
def init_lm_params(key, cfg, tp: int = 1, n_shards: int = 1, dtype=jnp.float32):
    dims = resolve_dims(cfg, tp, n_shards)
    keys = jax.random.split(key, 8)
    params = {
        "embed": dense_init(keys[0], (dims.vocab_loc, cfg.d_model), cfg.d_model, dtype),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            keys[1], (cfg.d_model, dims.vocab_loc), cfg.d_model, dtype
        )
    if cfg.family in ("dense", "vlm"):
        lk = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = jax.vmap(
            lambda k: _init_dense_layer(k, cfg, dims, dtype)
        )(lk)
    elif cfg.family == "moe":
        # the leading dense layers, then the MoE stack
        lk = jax.random.split(keys[2], cfg.n_layers - cfg.first_k_dense)
        params["layers"] = jax.vmap(lambda k: _init_moe_layer(k, cfg, dims, dtype))(lk)
        if cfg.first_k_dense:
            dk = jax.random.split(keys[3], cfg.first_k_dense)
            params["dense_layers"] = jax.vmap(lambda k: _init_dense_layer(
                k, cfg, dims, dtype, dims.ff_dense_loc))(dk)
    elif cfg.family == "hybrid":
        nb = cfg.n_layers // cfg.attn_every
        lk = jax.random.split(keys[2], cfg.n_layers)
        stacked = jax.vmap(lambda k: _init_mamba_layer(k, cfg, dims, dtype))(lk)
        params["layers"] = jax.tree.map(
            lambda x: x.reshape((nb, cfg.attn_every) + x.shape[1:]), stacked
        )
        params["shared_attn"] = _init_shared_attn(keys[3], cfg, dims, dtype)
    elif cfg.family == "ssm":
        nb = cfg.n_layers // 3
        lk = jax.random.split(keys[2], nb)
        params["layers"] = jax.vmap(lambda k: _init_xlstm_block(k, cfg, dims, dtype))(lk)
    else:
        raise ValueError(cfg.family)
    if cfg.frontend == "vit":
        params["frontend_proj"] = dense_init(
            keys[4], (cfg.frontend_dim, cfg.d_model), cfg.frontend_dim, dtype
        )
    return params


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------
def _remat(cfg):
    """Layer-granularity rematerialization with an optional policy that
    saves TP psum outputs (skips re-running collectives in backward)."""
    if getattr(cfg, "remat_policy", "full") == "save_psum":
        return partial(
            jax.checkpoint,
            policy=jax.checkpoint_policies.save_only_these_names("tp_psum"),
        )
    return jax.checkpoint


def _embed_inputs(params, batch, axes, cfg):
    """Returns (x (B,T,d), positions (B,T))."""
    tokens = batch["tokens"]
    x = embed_lookup(params["embed"], tokens, axes)
    if cfg.frontend == "vit":
        pe = batch["patch_embeds"].astype(x.dtype)
        pe = jnp.einsum("bnd,dk->bnk", pe, params["frontend_proj"].astype(x.dtype))
        x = jnp.concatenate([pe, x], axis=1)
    b, t = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    return x, positions


def _scan_layers(layer_fn, x, stacked, positions, axes, cfg, dims):
    """x through the stacked layers, each rematerialised in the backward
    pass; layer_fn(p, h, ...) -> (h, per-layer outputs)."""
    ckpt = _remat(cfg)

    def body(h, lp):
        return ckpt(
            lambda hh, pp: layer_fn(pp, hh, positions, axes, cfg, dims)
        )(h, lp)

    return lax.scan(body, x, stacked)


def lm_forward(params, batch, axes: Axes, cfg, dtype=jnp.bfloat16):
    """Returns hidden states after final norm: (B, T', d)."""
    return lm_forward_stats(params, batch, axes, cfg, dtype)[0]


def lm_forward_stats(params, batch, axes: Axes, cfg, dtype=jnp.bfloat16):
    """(hidden states after the final norm (B, T', d), stats): for an MoE
    model the balance loss summed over layers (``aux``) and, for the held
    experts' layer, the rows routed to each held expert (``expert_rows``,
    (MoE layers, held) int32); else {}."""
    tp = axes.tp_size
    dims = resolve_dims(cfg, tp, tp)
    x, positions = _embed_inputs(params, batch, axes, cfg)
    x = x.astype(dtype)
    stats = {}

    if cfg.family in ("dense", "vlm"):
        dense = lambda *a: (_dense_layer(*a), None)
        x, _ = _scan_layers(dense, x, params["layers"], positions, axes, cfg, dims)
    elif cfg.family == "moe":
        if cfg.first_k_dense:
            dense = lambda *a: (_dense_layer(*a), None)
            x, _ = _scan_layers(dense, x, params["dense_layers"], positions,
                                axes, cfg, dims)
        x, (aux, rows) = _scan_layers(_moe_layer, x, params["layers"],
                                      positions, axes, cfg, dims)
        stats["aux"] = jnp.sum(aux)
        if rows is not None:
            stats["expert_rows"] = rows
    elif cfg.family == "hybrid":
        emb0 = x

        def mamba_body(h, lp):
            h = h + ssm_mod.mamba2_train(
                lp["m"], rmsnorm(h, lp["ln"]), axes,
                n_heads_local=dims.ssm_heads_loc, head_dim=dims.ssm_head_dim,
                d_state=cfg.ssm_state,
            )
            return h, None

        def block_body(h, bp):
            h, _ = lax.scan(mamba_body, h, bp)
            h = _shared_attn_block(
                params["shared_attn"], h, emb0, positions, axes, cfg, dims
            )
            return h, None

        x, _ = lax.scan(block_body, x, params["layers"])
    elif cfg.family == "ssm":

        def body(h, bp):
            return _xlstm_block(bp, h, axes, cfg, dims), None

        x, _ = lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["ln_f"]), stats


def lm_logits_local(params, h, cfg):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("btd,dv->btv", h, head.astype(h.dtype)).astype(jnp.float32)


def lm_loss(params, batch, axes: Axes, cfg, dtype=jnp.bfloat16):
    return lm_loss_stats(params, batch, axes, cfg, dtype)[0]


def lm_loss_stats(params, batch, axes: Axes, cfg, dtype=jnp.bfloat16):
    """(mean next-token cross entropy plus an MoE model's balance loss,
    the stats of ``lm_forward_stats``)."""
    h, stats = lm_forward_stats(params, batch, axes, cfg, dtype)
    if cfg.frontend == "vit":  # only text positions carry labels
        h = h[:, -batch["tokens"].shape[1] :]
    logits = lm_logits_local(params, h, cfg)
    labels = batch["labels"]
    per_tok = tp_cross_entropy(logits, labels, axes)
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + stats.pop("aux", 0.0), stats
