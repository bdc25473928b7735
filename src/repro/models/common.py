"""Shared model machinery: TP-aware primitives usable both on a single
device (Axes(tp=None), smoke tests) and inside shard_map on the production
mesh (explicit psum over the `model` axis).

Sharding convention (Megatron-style):
  * embeddings: vocab dim sharded over TP; lookup masks out-of-slice ids and
    psums partial rows;
  * attention QKV: column-parallel (heads sharded); out-proj: row-parallel
    (+psum);
  * MLP in: column-parallel; MLP out: row-parallel (+psum);
  * norms / scalars: replicated;
  * logits: column-parallel (vocab sharded) + the Megatron parallel CE that
    never materializes gathered logits.

Head padding: when num_heads % tp != 0 we pad Q heads (zero-out-proj rows →
mathematically a no-op) and pad KV heads to the TP size as independent heads,
keeping every parameter either fully sharded or fully replicated over the
model axis (required so gradient aggregation semantics stay uniform). See
DESIGN.md §Hardware adaptation.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel import collectives as coll


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Axes:
    """Names of mesh axes visible inside the step function. All static."""

    tp: Optional[str] = dataclasses.field(default=None, metadata=dict(static=True))
    tp_size: int = dataclasses.field(default=1, metadata=dict(static=True))
    # sequence-parallel axes for long-context decode (KV shards); the data
    # axes re-purposed when batch==1. Tuple because multi-pod re-uses
    # ("pod","data") jointly.
    sp: tuple = dataclasses.field(default=(), metadata=dict(static=True))
    sp_sizes: tuple = dataclasses.field(default=(), metadata=dict(static=True))

    @property
    def sp_size(self) -> int:
        out = 1
        for s in self.sp_sizes:
            out *= s
        return out

    def tp_index(self):
        return lax.axis_index(self.tp) if self.tp else jnp.zeros((), jnp.int32)

    def sp_index(self):
        idx = jnp.zeros((), jnp.int32)
        for ax, size in zip(self.sp, self.sp_sizes):
            idx = idx * size + lax.axis_index(ax)
        return idx

    def psum_tp(self, x):
        if not self.tp:
            return x
        # named so a remat policy can SAVE collective outputs instead of
        # re-running them in the backward pass (§Perf "save_psum" policy)
        from jax.ad_checkpoint import checkpoint_name

        return checkpoint_name(coll.psum(x, self.tp), "tp_psum")

    def pmax_tp(self, x):
        return coll.pmax(x, self.tp) if self.tp else x


SINGLE = Axes()


def pad_to_multiple(n: int, m: int) -> int:
    return (n + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class HeadLayout:
    """Resolved (padded) head counts for a TP degree."""

    n_q: int  # padded global Q heads
    n_kv: int  # padded global KV heads
    head_dim: int
    q_local: int
    kv_local: int

    @property
    def group(self) -> int:
        return self.n_q // self.n_kv


def plan_heads(n_q: int, n_kv: int, head_dim: int, tp: int) -> HeadLayout:
    q_pad = pad_to_multiple(n_q, tp)
    kv_pad = n_kv if n_kv % tp == 0 or tp % 1 != 0 else n_kv
    if kv_pad % tp != 0 and tp % kv_pad == 0:
        kv_pad = tp  # pad KV heads up to one per device
    elif kv_pad % tp != 0:
        kv_pad = pad_to_multiple(n_kv, tp)
    # ensure group divides evenly
    if q_pad % kv_pad != 0:
        q_pad = pad_to_multiple(q_pad, kv_pad)
        q_pad = pad_to_multiple(q_pad, tp)
    return HeadLayout(q_pad, kv_pad, head_dim, q_pad // tp, kv_pad // tp)


# --------------------------------------------------------------------------
# initializers (local-shard aware: callers pass the LOCAL shape)
# --------------------------------------------------------------------------
def dense_init(key, shape, in_dim, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(max(in_dim, 1))
    return jax.random.uniform(key, shape, dtype, -scale, scale)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rmsnorm(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, w, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------
def rope(x, positions, theta: float = 10000.0, inv_freq=None):
    """x: (..., T, H, dh); positions: (..., T) int32. The two halves of
    each head rotate together, at `inv_freq` (dh/2 of them) where given,
    else at theta^(-j/(dh/2))."""
    dh = x.shape[-1]
    half = dh // 2
    if inv_freq is not None:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    else:
        freqs = jnp.exp(
            -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
        )
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., T, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# TP embedding lookup + parallel cross entropy
# --------------------------------------------------------------------------
def embed_lookup(table_local, ids, axes: Axes):
    """table_local: (V/tp, d); ids: (...) int32 global vocab ids."""
    v_local = table_local.shape[0]
    start = axes.tp_index() * v_local
    local_ids = ids - start
    ok = (local_ids >= 0) & (local_ids < v_local)
    rows = jnp.take(table_local, jnp.clip(local_ids, 0, v_local - 1), axis=0)
    rows = jnp.where(ok[..., None], rows, 0)
    return axes.psum_tp(rows)


def tp_cross_entropy(logits_local, labels, axes: Axes):
    """Megatron parallel softmax CE. logits_local: (..., V/tp) f32;
    labels: (...) global ids. Returns per-token loss (...)."""
    v_local = logits_local.shape[-1]
    start = axes.tp_index() * v_local
    logits_local = logits_local.astype(jnp.float32)
    # stabilizer only — not a differentiable path (pmax has no JVP rule)
    local_max = jax.lax.stop_gradient(jnp.max(logits_local, axis=-1))
    gmax = axes.pmax_tp(local_max)
    shifted = logits_local - gmax[..., None]
    sumexp = axes.psum_tp(jnp.sum(jnp.exp(shifted), axis=-1))
    local_labels = labels - start
    ok = (local_labels >= 0) & (local_labels < v_local)
    picked = jnp.take_along_axis(
        shifted, jnp.clip(local_labels, 0, v_local - 1)[..., None], axis=-1
    )[..., 0]
    picked = axes.psum_tp(jnp.where(ok, picked, 0.0))
    return jnp.log(sumexp) - picked


# --------------------------------------------------------------------------
# parallel dense helpers (inside shard_map the weights are already local)
# --------------------------------------------------------------------------
def col_parallel(x, w, axes: Axes, b=None):
    """x: (..., d_in) replicated; w: (d_in, d_out/tp) local. Out: sharded."""
    y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def row_parallel(x, w, axes: Axes, b=None):
    """x: (..., d_in/tp) sharded; w: (d_in/tp, d_out) local. Out: replicated."""
    y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
    y = axes.psum_tp(y)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def swiglu(gate, up):
    return jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
