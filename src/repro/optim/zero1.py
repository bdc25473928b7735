"""ZeRO-1: the f32 master weights and the base optimizer's state sharded
over the data-parallel axes; the parameters the step computes with are
replicated, in the dtype the trainer passes (f32 in every trainer of this
repo).

Each dp member holds 1/n_dp of every leaf's master and state, in one of two
storage layouts, chosen per leaf by :func:`zero1_view` from the leaf's
local shape, n_dp and tp alone:

  native  the leaf's 2-D view (rows = every axis but the last merged,
          C = the last axis) split into n_dp blocks of whole rows: member j
          holds rows [j·rows/n_dp, (j+1)·rows/n_dp) as (rows/n_dp, C);
          global (n_dp, rows/n_dp, C), PartitionSpec (dp_axes, None, None).
          Taken where tp == 1, the leaf has two axes or more, n_dp divides
          its rows, and either n_dp == 1 (the block is the whole leaf) or
          each block is whole (8, 128) tiles of the leaf's row-major layout:
          (rows/n_dp) % 8 == 0 and C % 128 == 0. (A leaf whose C is not a
          multiple of 128 the TPU may hold with its rows minor, as XLA lays
          out (., 2048, 64); a block of its rows is then no whole tiles.)
          No flatten, pad or relayout on either side of the update: ĝ's
          block is a slice of its 2-D view, the new parameters are the
          blocks gathered along that axis.
  flat    the LOCAL TP shard's elements in row-major order, padded to
          n_dp·⌈k/n_dp⌉ and split into n_dp rows: member j holds row j,
          (k_loc/n_dp,); global (n_dp, tp·k_loc/n_dp), PartitionSpec
          (dp_axes, "model"). Every other leaf: the 1-D norm scales,
          leaves n_dp does not split into whole tiles, and tp > 1.

Where n_dp divides the rows without padding, member j's native block holds
the same elements, in the same order, as its flat row, so both layouts
update each element from the same operands to the same result.

Step protocol (inside shard_map):
    1. each member takes its share of ĝ (decoded IntSGD aggregate, identical
       on all dp members): a block of its 2-D view, or a row of its padded
       flat elements;
    2. the base optimizer update runs elementwise on the f32 share;
    3. the new shares are all-gathered over dp along their leading axis
       and reshaped to the leaf (the flat rows lose their padding first).
No step ever forms the (n_dp, k/n_dp) view of a flat leaf: the TPU compiler
spends minutes on such a view of a large leaf, and seconds on a 1-D slice
and a tiled gather. For the same reason a flat leaf of stacked layers is
flattened from its 2-D view (``_flat``).
The all-gather is the only extra collective ZeRO-1 introduces.
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


def zero1_view(shape, n_dp: int, tp: int) -> str:
    """How ZeRO-1 holds a leaf of local `shape` over `n_dp` dp members at
    tensor parallelism `tp`: "native" (blocks of the leaf's own rows) or
    "flat" (rows of its flattened elements); see the module docstring."""
    if tp != 1 or len(shape) < 2:
        return "flat"
    per, rem = divmod(math.prod(shape[:-1]), n_dp)
    whole_tiles = per % 8 == 0 and shape[-1] % 128 == 0
    return "flat" if rem or (n_dp > 1 and not whole_tiles) else "native"


def _rows(x):
    """x's 2-D view: every axis but the last merged, a relabelling of the
    same tiles."""
    return x.reshape(-1, x.shape[-1])


def _flat(x):
    """x in one row, in row-major order. A leaf of stacked layers goes
    through its 2-D view behind an optimization barrier: the TPU compiler
    takes minutes to relayout a stacked 3-D or 4-D leaf straight into a row
    (50 to 170 s a leaf at DeepSeek-V2-Lite's widths), and a second from
    2-D."""
    if x.ndim > 2 and math.prod(x.shape[:-2]) > 1:
        x = lax.optimization_barrier(_rows(x))
    return x.reshape(-1)


def _pad_rows(flat, n_dp):
    k = flat.shape[0]
    per = (k + n_dp - 1) // n_dp
    return jnp.pad(flat, (0, per * n_dp - k)).reshape(n_dp, per)


def shard_leaf(x, n_dp):
    """param leaf -> (n_dp, k/n_dp) f32 flat master layout (shapes only:
    the state builders take one row each with :func:`own_row`)."""
    return _pad_rows(_flat(x).astype(jnp.float32), n_dp)


def own_row(x, n_dp, dp_index):
    """This dp member's (k/n_dp,) f32 row of the flat master layout of
    leaf x."""
    flat = _flat(x).astype(jnp.float32)
    per = -(-flat.size // n_dp)
    flat = jnp.pad(flat, (0, per * n_dp - flat.size))
    return lax.dynamic_slice_in_dim(flat, dp_index * per, per)


def own_block(x, n_dp, dp_index):
    """This dp member's (rows/n_dp, C) f32 block of the native master
    layout of leaf x."""
    rows = _rows(x).astype(jnp.float32)
    per = rows.shape[0] // n_dp
    return lax.dynamic_slice_in_dim(rows, dp_index * per, per)


def own_share(x, n_dp, dp_index, tp):
    """This dp member's share of leaf x's master layout, as
    :func:`zero1_view` picks it: a block of rows or a flat row."""
    if zero1_view(x.shape, n_dp, tp) == "native":
        return own_block(x, n_dp, dp_index)
    return own_row(x, n_dp, dp_index)


def zero1_init(base: Optimizer, params, n_dp: int, tp: int):
    """The global ZeRO-1 state of local param leaves (shapes only)."""
    def master(p):
        if zero1_view(p.shape, n_dp, tp) == "native":
            return p.astype(jnp.float32).reshape(n_dp, -1, p.shape[-1])
        return shard_leaf(p, n_dp)

    masters = jax.tree.map(master, params)
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
    tp: int,
    param_dtype=jnp.bfloat16,
    params_like=None,
):
    """Returns (new_params, new_state). Runs INSIDE shard_map.

    state leaves carry a leading local dp dim of 1 (the device's own
    share); ghat is the full local-TP gradient tree."""
    masters = state["master"]

    def local(leaf):  # (1, ...) local -> (...); scalars (adam count) pass through
        return leaf[0] if leaf.ndim >= 2 else leaf

    g_own = jax.tree.map(lambda g: own_share(g, n_dp, dp_index, tp), ghat)
    m_own = jax.tree.map(local, masters)
    b_own = jax.tree.map(local, state["base"])
    updates, new_base = base.update(g_own, b_own, m_own, eta)
    new_master = jax.tree.map(lambda m, u: m + u, m_own, updates)

    def gather_param(own, p_like):
        full = coll.all_gather_concat(own.astype(param_dtype), dp_axes)
        if full.ndim == 1:  # a flat row: drop the padding
            full = full[: p_like.size]
        return full.reshape(p_like.shape)

    new_params = jax.tree.map(gather_param, new_master, params_like)
    restack = lambda t: jax.tree.map(lambda x: x[None] if x.ndim >= 1 else x, t)
    return new_params, {"master": restack(new_master), "base": restack(new_base)}


def zero1_state_specs(state_shapes, dp_spec, model_axis="model"):
    """PartitionSpecs for a zero1 state tree (from eval_shape shapes).
    model_axis=None (tp==1 axis-remap mode, the only one with native
    blocks): every axis after the dp one replicated."""
    from jax.sharding import PartitionSpec as P

    def spec(leaf):
        if leaf.ndim >= 2:
            return P(dp_spec, model_axis, *[None] * (leaf.ndim - 2))
        return P()

    return jax.tree.map(spec, state_shapes)
