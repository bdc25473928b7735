"""Mixture-of-Experts: a held-experts layer that drops no token, and two
capacity-buffer sharding strategies.

  * ``held`` (tp == 1, e.g. DeepSeek-V2-Lite at one chip's share of expert
    parallelism): the layer is told which experts it holds (the first
    ``n_held`` from ``first``), routes every token over all ``n_experts``,
    and computes only its held experts' part: the (token, choice) pairs
    that land on a held expert are grouped by expert and sent through
    grouped SwiGLU products (megablox's ``gmm``) over the rows actually
    routed there, scaled by their gate weights and added back to their
    tokens. No token is dropped under any imbalance: a loop takes the
    grouped rows ``CHUNK`` at a time, for as many passes as the routed
    rows need. Each pass runs the products over all of its rows, the
    unrouted ones masked, so the work costs the routed rows rounded up to
    whole passes, the memory one pass, and a pass's time does not follow
    the router's load. On one chip there is no exchange;
    the absent experts' part is left out, as expert parallelism would
    leave it to their chips.

  * ``ep``  (n_experts % tp == 0, e.g. deepseek 64 experts / 16 devices):
    classic expert parallelism — each device owns n_experts/tp experts;
    tokens are dispatched with a capacity-factor buffer and exchanged via
    all_to_all over the model axis, expert FFN runs on the owner, results
    come back via the reverse all_to_all.

  * ``tp``  (n_experts < tp, e.g. mixtral 8 experts / 16 devices): every
    expert's FFN is tensor-sharded over the full model axis; tokens are
    gathered per-expert into capacity buffers locally (no all_to_all) and
    each expert runs as a column→row parallel MLP. Avoids replicated expert
    weights, keeping the "sharded or replicated" parameter invariant.

All route by top-k over a softmax of all experts, renormalising the k gate
weights where the configuration says so (``norm_topk_prob``); ``ep`` and
``tp`` drop tokens at capacity (Switch/Mixtral-style). Shared experts
(DeepSeek-V2) are plain TP MLPs added unconditionally, once. The
sequence-wise balance loss (DeepSeek-V2's ``seq_aux``) is computed from the
full-width router.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.kernels import ops as kernel_ops
from repro.launch import stages
from repro.parallel import collectives as coll

from repro.models.common import Axes, swiglu
from repro.models.mlp import swiglu_mlp


def pick_strategy(n_experts: int, tp: int) -> str:
    if tp == 1:
        return "held"
    return "ep" if n_experts % tp == 0 else "tp"


def route(router_w, x, top_k: int, norm_topk_prob: bool):
    """x: (N, d) -> (probs (N, E), weights (N, k), ids (N, k)): a softmax
    over every expert in f32, its greedy top-k, renormalised over the k
    where `norm_topk_prob`."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = lax.top_k(probs, top_k)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return probs, w, ids


def balance_loss(probs, ids, n_seq: int):
    """DeepSeek-V2's sequence-wise balance loss (before its coefficient):
    per sequence, sum_i f_i P_i, with f_i the share of the sequence's
    top-k choices that picked expert i times E / k (a count: no gradient)
    and P_i the mean of its probability over the sequence's tokens;
    averaged over sequences. probs: (N, E) with N = n_seq x T tokens,
    sequence-major; ids: (N, k)."""
    n, e = probs.shape
    k = ids.shape[1]
    t = n // n_seq
    picks = jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=1)
    f = jnp.sum(picks.reshape(n_seq, t, e), axis=1) * (e / (t * k))
    p = jnp.mean(probs.reshape(n_seq, t, e), axis=1)
    return jnp.mean(jnp.sum(lax.stop_gradient(f) * p, axis=-1))


# ---------------------------------------------------------------------------
# the held experts: grouped by expert, dropless
# ---------------------------------------------------------------------------
CHUNK = 8192  # grouped rows per pass of the held experts' loop


def _plan(ids, first: int, n_held: int, size: int):
    """Where each (token, choice) pair goes in the grouped rows.

    Returns ``order`` (size,): the pair in each row, the held experts'
    pairs first, grouped by expert in order and by token within one, the
    rest of the rows 0; ``sizes`` (n_held,): rows per held expert; and
    ``n_rows``, their sum."""
    local = ids.reshape(-1) - first
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)
    onehot = jax.nn.one_hot(key, n_held + 1, dtype=jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    sizes = jnp.sum(onehot, axis=0)[:n_held]
    starts = jnp.cumsum(sizes) - sizes
    row = jnp.where(held, starts[jnp.minimum(key, n_held - 1)] + rank, size)
    pairs = jnp.arange(key.shape[0], dtype=jnp.int32)
    order = jnp.zeros((size,), jnp.int32).at[row].set(pairs, mode="drop")
    return order, sizes, jnp.sum(sizes)


def _tile(dim: int) -> int:
    """A grouped product's tile along a weight dimension: 512 where it
    divides, else the whole dimension where it is a multiple of 128 (1408
    is 11 x 128), else 128."""
    if dim % 512 == 0:
        return 512
    return dim if dim % 128 == 0 and dim <= 2048 else min(dim, 128)


def _tiling(m: int, k: int, n: int):
    """(rows, contraction, columns) tiles of megablox's grouped products,
    looked up per call, so the backward's transposed products get their
    own."""
    tm = next((t for t in (512, 256, 128) if m % t == 0), m)
    return tm, _tile(k), _tile(n)


def _gmm(x, w, sizes):
    return megablox.gmm(x, w.astype(x.dtype), sizes, x.dtype, _tiling,
                        None, None, False,
                        kernel_ops._interpret_default())


def _grouped_swiglu(xs, sizes, wg, wu, wd):
    """Each held expert's SwiGLU over its own rows of xs (grouped by
    expert, ``sizes`` rows each): megablox's grouped products, whose grid
    visits the tiles that the groups cover. Rows past the sum of ``sizes``
    are left unwritten."""
    return _gmm(swiglu(_gmm(xs, wg, sizes), _gmm(xs, wu, sizes)), wd, sizes)


def _chunk(i, c, k, xf, w, order, sizes, n_rows):
    """Grouped rows i·c .. i·c + c - 1: their pair, token, whether routed,
    gate weight (0 where not), input, and rows per held expert."""
    lo = i * c
    pair = lax.dynamic_slice_in_dim(order, lo, c)
    tok = pair // k
    valid = lo + jnp.arange(c) < n_rows
    ends = jnp.cumsum(sizes)
    in_chunk = (jnp.clip(ends, lo, lo + c)
                - jnp.clip(ends - sizes, lo, lo + c)).astype(jnp.int32)
    # the last held expert's group takes the pass's unrouted rows too, so
    # the grouped products visit every tile of the pass whatever the load
    in_chunk = in_chunk.at[-1].add(c - jnp.sum(in_chunk))
    return pair, tok, valid, jnp.where(valid, w[pair], 0.0), xf[tok], in_chunk


def _n_chunks(c, n_rows):
    return (n_rows + c - 1) // c


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_part(c, k, xf, w, order, sizes, n_rows, wg, wu, wd):
    """The held experts' part (N, d) of the layer's output: a loop over the
    grouped rows, `c` at a time, for as many passes as the routed rows
    need, so the work follows the routed rows in whole passes and memory
    one pass."""
    def body(i, out):
        with stages.scope("moe_route"):
            _, tok, valid, gate, xs, in_chunk = _chunk(
                i, c, k, xf, w, order, sizes, n_rows)
        with stages.scope("moe_experts"):
            ys = _grouped_swiglu(xs, in_chunk, wg, wu, wd)
        with stages.scope("moe_route"):
            ys = jnp.where(valid[:, None], ys.astype(jnp.float32), 0.0)
            return out.at[tok].add(ys * gate[:, None])

    out = lax.fori_loop(0, _n_chunks(c, n_rows), body,
                        jnp.zeros(xf.shape, jnp.float32))
    return out.astype(xf.dtype)


def _held_fwd(c, k, *args):
    return _held_part(c, k, *args), args


def _held_bwd(c, k, res, dout):
    """The cotangents of xf, w and the experts' weights, each pass's
    products recomputed over its own rows."""
    xf, w, order, sizes, n_rows, wg, wu, wd = res

    def body(i, acc):
        dxf, dw, dwg, dwu, dwd = acc
        with stages.scope("moe_route"):
            pair, tok, valid, gate, xs, in_chunk = _chunk(
                i, c, k, xf, w, order, sizes, n_rows)
            dyw = dout[tok].astype(jnp.float32)
        with stages.scope("moe_experts"):
            ys, pull = jax.vjp(
                lambda a, b, g, h: _grouped_swiglu(a, in_chunk, b, g, h),
                xs, wg, wu, wd)
        with stages.scope("moe_route"):
            dgate = jnp.sum(dyw * ys.astype(jnp.float32), axis=-1)
            dw = dw.at[pair].add(jnp.where(valid, dgate, 0.0))
            dys = (dyw * gate[:, None]).astype(ys.dtype)
        with stages.scope("moe_experts"):
            dxs, gg, gu, gd = pull(dys)
        with stages.scope("moe_route"):
            dxs = jnp.where(valid[:, None], dxs.astype(jnp.float32), 0.0)
            return dxf.at[tok].add(dxs), dw, dwg + gg, dwu + gu, dwd + gd

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    dxf, dw, dwg, dwu, dwd = lax.fori_loop(
        0, _n_chunks(c, n_rows), body,
        (zeros(xf), zeros(w), zeros(wg), zeros(wu), zeros(wd)))
    return (dxf.astype(xf.dtype), dw.astype(w.dtype), None, None, None,
            dwg.astype(wg.dtype), dwu.astype(wu.dtype), dwd.astype(wd.dtype))


_held_part.defvjp(_held_fwd, _held_bwd)


def moe_held(params, x, *, n_experts, top_k, norm_topk_prob=True,
             aux_alpha=0.0, first=0):
    """The held experts' layer, dropless. x: (B, T, d). Returns (out
    (B, T, d), aux loss, rows per held expert (n_held,) int32)."""
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    n_held = params["w_gate"].shape[0]
    # every pair that can land here: n·min(k, held) rows, in whole passes
    top = n * min(top_k, n_held)
    c = min(CHUNK, top)
    with stages.scope("moe_route"):
        probs, w, ids = route(params["router"], xf, top_k, norm_topk_prob)
        aux = aux_alpha * balance_loss(probs, ids, b) if aux_alpha else 0.0
        order, sizes, n_rows = _plan(ids, first, n_held, -(-top // c) * c)
    out = _held_part(c, top_k, xf, w.reshape(-1), order, sizes, n_rows,
                     params["w_gate"], params["w_up"], params["w_down"])
    out = out.reshape(b, t, d)
    if "shared" in params:
        out = out + swiglu_mlp(params["shared"], x, Axes())
    return out, aux, sizes


def _dispatch_indices(ids, w, n_experts, capacity):
    """Compute per-(token,choice) target slot within its expert's capacity
    buffer; over-capacity tokens are dropped (weight zeroed)."""
    n, k = ids.shape
    flat_e = ids.reshape(-1)  # (N*k,)
    # position of each (token,choice) within its expert's arrival order
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)  # (N*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot  # exclusive prefix count
    slot = jnp.sum(pos_in_e * onehot, axis=-1)  # (N*k,)
    keep = slot < capacity
    return flat_e, jnp.where(keep, slot, capacity - 1), keep


def moe_tp(params, x, axes: Axes, *, n_experts, top_k, capacity_factor=1.25,
           norm_topk_prob=True):
    """TP-strategy MoE. x: (B, T, d) replicated across TP."""
    b, t, d = x.shape
    n = b * t
    xf = x.reshape(n, d)
    _, w, ids = route(params["router"], xf, top_k, norm_topk_prob)
    capacity = max(8, int(n * top_k * capacity_factor / n_experts))
    flat_e, slot, keep = _dispatch_indices(ids, w, n_experts, capacity)
    # scatter tokens into (E, C, d) buffers
    buf = jnp.zeros((n_experts, capacity, d), x.dtype)
    src = jnp.repeat(xf, top_k, axis=0)  # (N*k, d) token per choice
    buf = buf.at[flat_e, slot].add(jnp.where(keep[:, None], src, 0))
    # per-expert column->row parallel SwiGLU (batched over experts)
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(x.dtype))
    h = swiglu(g, u)
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(x.dtype))
    out_buf = axes.psum_tp(out_buf)
    # gather back with routing weights
    picked = out_buf[flat_e, slot]  # (N*k, d)
    wk = (w.reshape(-1) * keep).astype(x.dtype)
    out = jnp.sum((picked * wk[:, None]).reshape(n, top_k, d), axis=1)
    out = out.reshape(b, t, d)
    if "shared" in params:
        out = out + swiglu_mlp(params["shared"], x, axes)
    return out


def moe_ep(params, x, axes: Axes, *, n_experts, top_k, capacity_factor=1.25,
           norm_topk_prob=True):
    """EP-strategy MoE: experts sharded over the model axis; token exchange
    via all_to_all. x: (B, T, d) replicated across TP (each TP member handles
    an equal slice of local tokens to avoid duplicate compute)."""
    tp = axes.tp_size
    b, t, d = x.shape
    n_all = b * t
    xf = x.reshape(n_all, d)
    # each TP member routes its 1/tp slice of the tokens
    if axes.tp:
        n = n_all // tp
        start = axes.tp_index() * n
        xf = lax.dynamic_slice_in_dim(xf, start, n, axis=0)
    else:
        n = n_all
    _, w, ids = route(params["router"], xf, top_k, norm_topk_prob)
    e_loc = n_experts // tp
    # capacity per (device, expert) buffer
    capacity = max(8, int(n * top_k * capacity_factor / n_experts))
    flat_e, slot, keep = _dispatch_indices(ids, w, n_experts, capacity)
    # dispatch buffer grouped by owner device: (tp, e_loc, C, d)
    buf = jnp.zeros((tp, e_loc, capacity, d), x.dtype)
    owner = flat_e // e_loc
    sub = flat_e % e_loc
    src = jnp.repeat(xf, top_k, axis=0)
    buf = buf.at[owner, sub, slot].add(jnp.where(keep[:, None], src, 0))
    if axes.tp:
        # exchange: device i sends buf[j] to device j -> receives (tp, e_loc, C, d)
        buf = coll.all_to_all(buf, axes.tp, split_axis=0, concat_axis=0, tiled=True)
        buf = buf.reshape(tp, e_loc, capacity, d)
    # expert FFN on owned experts over all received tokens: fold sender dim
    recv = buf.transpose(1, 0, 2, 3).reshape(e_loc, tp * capacity, d)
    g = jnp.einsum("ecd,edf->ecf", recv, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", recv, params["w_up"].astype(x.dtype))
    h = swiglu(g, u)
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(x.dtype))
    out_buf = out_buf.reshape(e_loc, tp, capacity, d).transpose(1, 0, 2, 3)
    if axes.tp:
        out_buf = coll.all_to_all(out_buf, axes.tp, split_axis=0, concat_axis=0, tiled=True)
        out_buf = out_buf.reshape(tp, e_loc, capacity, d)
    picked = out_buf[owner, sub, slot]
    wk = (w.reshape(-1) * keep).astype(x.dtype)
    out = jnp.sum((picked * wk[:, None]).reshape(n, top_k, d), axis=1)
    if axes.tp:
        # re-assemble the full token set across TP members
        full = jnp.zeros((n_all, d), x.dtype)
        full = lax.dynamic_update_slice_in_dim(full, out, axes.tp_index() * n, axis=0)
        out = axes.psum_tp(full)
    out = out.reshape(b, t, d)
    if "shared" in params:
        out = out + swiglu_mlp(params["shared"], x, axes)
    return out


def moe_block(params, x, axes: Axes, cfg):
    """The configuration's MoE layer. Returns (out, aux loss, rows per held
    expert or None where the strategy drops tokens at capacity)."""
    strategy = pick_strategy(cfg.n_experts, axes.tp_size)
    if strategy == "held":
        return moe_held(
            params, x, n_experts=cfg.n_experts, top_k=cfg.top_k,
            norm_topk_prob=cfg.norm_topk_prob, aux_alpha=cfg.aux_loss_alpha,
        )
    fn = moe_ep if strategy == "ep" else moe_tp
    out = fn(
        params, x, axes, n_experts=cfg.n_experts, top_k=cfg.top_k,
        norm_topk_prob=cfg.norm_topk_prob,
    )
    return out, 0.0, None
