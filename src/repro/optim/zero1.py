"""ZeRO-1: optimizer state (f32 master weights + moments) sharded over the
data-parallel axes; bf16 compute params replicated.

This is what makes the 32B-class configs fit 16 GB/chip: per device the
footprint is bf16_params/TP + 2·f32_state/(TP·DP) instead of
3·f32_params/TP.

Storage layout per parameter leaf (LOCAL TP shard flattened and padded):
    master, moments: (n_dp, k_loc/n_dp)   — global (n_dp, tp·k_loc/n_dp),
                                            PartitionSpec (dp_axes, "model")

Step protocol (inside shard_map):
    1. ĝ (decoded IntSGD aggregate, identical on all dp members) is flattened
       and padded to n_dp·(k/n_dp), and each member slices out ITS row;
    2. the base optimizer update runs on the f32 shard;
    3. the new bf16 shard is all-gathered over dp → full new params.
No step ever forms the (n_dp, k/n_dp) view of a full leaf: the TPU compiler
spends minutes on such a view of a large leaf, and seconds on a 1-D slice
and a tiled gather. For the same reason a leaf of stacked layers is
flattened from its 2-D view (``_flat``).
The all-gather is bf16 (half the bytes of the f32 gradient it replaces in a
ZeRO-less design) and is the only extra collective ZeRO-1 introduces.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.launch import stages
from repro.optim.base import Optimizer
from repro.parallel import collectives as coll


def _flat(x):
    """x in one row, in row-major order. A leaf of stacked layers goes
    through its 2-D view (every axis but the last merged, a relabelling of
    the same tiles) behind an optimization barrier: the TPU compiler takes
    minutes to relayout a stacked 3-D or 4-D leaf straight into a row (50
    to 170 s a leaf at DeepSeek-V2-Lite's widths), and a second from 2-D."""
    if x.ndim > 2 and math.prod(x.shape[:-2]) > 1:
        x = lax.optimization_barrier(x.reshape(-1, x.shape[-1]))
    return x.reshape(-1)


def _pad_rows(flat, n_dp):
    k = flat.shape[0]
    per = (k + n_dp - 1) // n_dp
    return jnp.pad(flat, (0, per * n_dp - k)).reshape(n_dp, per)


def shard_leaf(x, n_dp):
    """param leaf -> (n_dp, k/n_dp) f32 master layout (shapes only: the
    state builders take one row each with :func:`own_row`)."""
    return _pad_rows(_flat(x).astype(jnp.float32), n_dp)


def own_row(x, n_dp, dp_index):
    """This dp member's (k/n_dp,) f32 row of the master layout of leaf x."""
    flat = _flat(x).astype(jnp.float32)
    per = -(-flat.size // n_dp)
    flat = jnp.pad(flat, (0, per * n_dp - flat.size))
    return lax.dynamic_slice_in_dim(flat, dp_index * per, per)


def zero1_init(base: Optimizer, params, n_dp: int):
    masters = jax.tree.map(lambda p: shard_leaf(p, n_dp), params)
    return {"master": masters, "base": base.init(masters)}


@stages.scoped("update")
def zero1_update(
    base: Optimizer,
    state,
    ghat,
    eta,
    *,
    dp_axes: Tuple[str, ...],
    dp_index,
    n_dp: int,
    param_dtype=jnp.bfloat16,
    params_like=None,
):
    """Returns (new_params, new_state). Runs INSIDE shard_map.

    state leaves carry a leading local dp dim of 1 (the device's own shard
    row); ghat is the full local-TP gradient tree."""
    masters = state["master"]

    def local_row(leaf):  # (1, k) local -> (k,); scalars (adam count) pass through
        return leaf[0] if leaf.ndim >= 2 else leaf

    g_rows = jax.tree.map(lambda g: own_row(g, n_dp, dp_index), ghat)
    m_rows = jax.tree.map(local_row, masters)
    b_rows = jax.tree.map(local_row, state["base"])
    updates, new_base = base.update(g_rows, b_rows, m_rows, eta)
    new_master = jax.tree.map(lambda m, u: m + u, m_rows, updates)

    def gather_param(mrow, p_like):
        full = coll.all_gather_concat(mrow.astype(param_dtype), dp_axes)
        return full[: p_like.size].reshape(p_like.shape)

    new_params = jax.tree.map(gather_param, new_master, params_like)
    restack = lambda t: jax.tree.map(lambda x: x[None] if x.ndim >= 1 else x, t)
    return new_params, {"master": restack(new_master), "base": restack(new_base)}


def zero1_state_specs(state_shapes, dp_spec, model_axis="model"):
    """PartitionSpecs for a zero1 state tree (from eval_shape shapes).
    model_axis=None (tp==1 axis-remap mode): dim1 replicated."""
    from jax.sharding import PartitionSpec as P

    def spec(leaf):
        if leaf.ndim >= 2:
            return P(dp_spec, model_axis) if model_axis else P(dp_spec, None)
        return P()

    return jax.tree.map(spec, state_shapes)
