"""Single-token decode (serve) paths for every family, with KV/state caches.

Cache layout per family (leading L or block axis scanned with the layers):
  dense/vlm : {"layers": {"k","v","kv_pos"}}                    (GQA KV)
  moe+mla   : {"layers": {"c_kv","k_r","kv_pos"}}               (MLA latent)
  hybrid    : {"mamba": conv/h stacked (nb, per, ...),
               "attn": KV per shared-attn application (nb, ...)}
  ssm/xlstm : {"blocks": {"m1","m2","s"} recurrent states}

The decode step lowers as `serve_step` in the dry-run for `decode_*` and
`long_*` shapes. For long_500k (batch=1) the KV sequence dim is sharded over
the data axis (axes.sp) with a distributed online softmax in attention.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import Axes, embed_lookup, rmsnorm
from repro.models.mlp import swiglu_mlp
from repro.models.transformer import (
    lm_logits_local,
    resolve_dims,
)


def init_lm_cache(cfg, tp: int, n_shards: int, b_local: int, s_local: int, dtype=jnp.bfloat16):
    dims = resolve_dims(cfg, tp, n_shards)
    L = cfg.n_layers

    def stack(tree, n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)

    if cfg.family in ("dense", "vlm"):
        if cfg.kv_lora:
            base = mla_mod.init_mla_cache(b_local, s_local, cfg.kv_lora, dtype)
        else:
            base = attn.init_cache(b_local, s_local, dims.layout, dtype)
        return {"layers": stack(base, L)}
    if cfg.family == "moe":
        if cfg.kv_lora:
            base = mla_mod.init_mla_cache(b_local, s_local, cfg.kv_lora, dtype)
        else:
            base = attn.init_cache(b_local, s_local, dims.layout, dtype)
        out = {"layers": stack(base, L - cfg.first_k_dense)}
        if cfg.first_k_dense:
            out["dense_layers"] = stack(base, cfg.first_k_dense)
        return out
    if cfg.family == "hybrid":
        nb = cfg.n_layers // cfg.attn_every
        m = ssm_mod.init_mamba2_cache(
            b_local, dims.ssm_heads_loc, dims.ssm_head_dim, cfg.ssm_state
        )
        a = attn.init_cache(b_local, s_local, dims.layout, dtype)
        return {
            "mamba": stack(stack(m, cfg.attn_every), nb),
            "attn": stack(a, nb),
        }
    if cfg.family == "ssm":
        nb = cfg.n_layers // 3
        blk = {
            "m1": xlstm_mod.init_mlstm_cache(b_local, dims.xl_heads_loc, dims.xl_head_dim),
            "m2": xlstm_mod.init_mlstm_cache(b_local, dims.xl_heads_loc, dims.xl_head_dim),
            "s": xlstm_mod.init_slstm_cache(b_local, dims.xl_heads_loc, dims.xl_head_dim),
        }
        return {"blocks": stack(blk, nb)}
    raise ValueError(cfg.family)


def _attn_decode_any(lp, h, pos, lc, axes, cfg, dims):
    if cfg.kv_lora:
        return mla_mod.mla_decode(
            lp, h, pos, lc, axes,
            n_heads_local=dims.layout.q_local, head_dim=dims.layout.head_dim,
            rot=mla_mod.rope_of(cfg),
        )
    return attn.attention_decode(
        lp, h, pos, lc, axes, dims.layout,
        window=cfg.window, rope_theta=cfg.rope_theta,
    )


def lm_decode_step(params, cache, tokens, pos, axes: Axes, cfg, dtype=jnp.bfloat16):
    """tokens: (B,) int32 ids of the current step; pos: (B,) positions.
    Returns (logits_local (B, V/tp) f32, new_cache)."""
    tp = axes.tp_size
    dims = resolve_dims(cfg, tp, tp)
    x = embed_lookup(params["embed"], tokens[:, None], axes).astype(dtype)

    if cfg.family in ("dense", "vlm", "moe"):

        def body(h, xs, moe):
            lp, lc = xs
            a, new_lc = _attn_decode_any(
                lp["attn"], rmsnorm(h, lp["ln1"]), pos, lc, axes, cfg, dims
            )
            h = h + a
            z = rmsnorm(h, lp["ln2"])
            if moe:
                h = h + moe_mod.moe_block(lp["moe"], z, axes, cfg)[0]
            else:
                h = h + swiglu_mlp(lp["mlp"], z, axes)
            return h, new_lc

        new_cache = {}
        if "dense_layers" in params:  # an MoE model's leading dense layers
            x, new_cache["dense_layers"] = lax.scan(
                partial(body, moe=False), x,
                (params["dense_layers"], cache["dense_layers"]))
        x, new_cache["layers"] = lax.scan(
            partial(body, moe=cfg.family == "moe"), x,
            (params["layers"], cache["layers"]))
    elif cfg.family == "hybrid":
        emb0 = x

        def mamba_body(h, xs):
            lp, lc = xs
            out, new_lc = ssm_mod.mamba2_decode(
                lp["m"], rmsnorm(h, lp["ln"]), lc, axes,
                n_heads_local=dims.ssm_heads_loc, head_dim=dims.ssm_head_dim,
                d_state=cfg.ssm_state,
            )
            return h + out, new_lc

        sp = params["shared_attn"]

        def block_body(h, xs):
            bp, bc_m, bc_a = xs
            h, new_m = lax.scan(mamba_body, h, (bp, bc_m))
            z = jnp.concatenate([h, emb0], axis=-1)
            z = rmsnorm(z, sp["ln"])
            z = jnp.einsum("btd,dk->btk", z, sp["w_in"].astype(z.dtype))
            a, new_a = attn.attention_decode(
                sp["attn"], z, pos, bc_a, axes, dims.layout, rope_theta=cfg.rope_theta
            )
            z = z + a
            z = z + swiglu_mlp(sp["mlp"], rmsnorm(z, sp["ln2"]), axes)
            return h + z, (new_m, new_a)

        x, (new_m, new_a) = lax.scan(
            block_body, x, (params["layers"], cache["mamba"], cache["attn"])
        )
        new_cache = {"mamba": new_m, "attn": new_a}
    elif cfg.family == "ssm":
        kw = dict(n_heads_local=dims.xl_heads_loc, head_dim=dims.xl_head_dim)

        def body(h, xs):
            bp, bc = xs
            o, c1 = xlstm_mod.mlstm_decode(
                bp["m1"]["cell"], rmsnorm(h, bp["m1"]["ln"]), bc["m1"], axes, **kw
            )
            h = h + o
            o, c2 = xlstm_mod.mlstm_decode(
                bp["m2"]["cell"], rmsnorm(h, bp["m2"]["ln"]), bc["m2"], axes, **kw
            )
            h = h + o
            o, c3 = xlstm_mod.slstm_decode(
                bp["s"]["cell"], rmsnorm(h, bp["s"]["ln"]), bc["s"], axes, **kw
            )
            h = h + o
            return h, {"m1": c1, "m2": c2, "s": c3}

        x, new_blocks = lax.scan(body, x, (params["layers"], cache["blocks"]))
        new_cache = {"blocks": new_blocks}
    else:
        raise ValueError(cfg.family)

    h = rmsnorm(x, params["ln_f"])
    logits = lm_logits_local(params, h, cfg)[:, 0]
    return logits, new_cache


def tp_greedy(logits_local, axes: Axes):
    """Greedy token from vocab-sharded logits without gathering them."""
    v_local = logits_local.shape[-1]
    local_best = jnp.argmax(logits_local, axis=-1)
    local_val = jnp.take_along_axis(logits_local, local_best[..., None], axis=-1)[..., 0]
    global_id = local_best + axes.tp_index() * v_local
    gmax = axes.pmax_tp(local_val)
    winner = jnp.where(local_val >= gmax, global_id, 0)
    return axes.psum_tp(winner) if axes.tp else winner
