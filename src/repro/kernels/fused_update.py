"""Pallas kernels: fused dequantize + optimizer step for the whole family.

The SGD form replaces the chain
    g  = Σints * 1/(nα)         (read int, write g)
    g += wd * p                 (read g, p, write g)
    m  = μ m + g                (read m, g, write m)
    p -= lr m                   (read p, m, write p)
— 9 HBM tensor touches — with a single pass: 3 reads (ints, p, m) and
2 writes (p', m'). On a memory-bound elementwise stage this is a ~1.8×
reduction in optimizer-step HBM traffic. The AdamW form fuses the
bias-corrected moment EMAs the same way (4 reads: ints, p, mu, nu; 3
writes: p', mu', nu' — the moments never leave registers between decode
and apply, vs 13 tensor touches unfused).

``fused_unpack_*_2d`` are the PackedInt-wire variants: they consume the
bit-packed int32 transport words straight off the all-reduce (d/k words
instead of d integer lanes read from HBM), unpacking k bias-shifted fields
per word in-register before the identical update arithmetic — so the packed
route never materializes the integer image at all.

Shift (IntDIANA): with ``has_shift`` every kernel takes one extra f32
tensor h (the replicated global shift) and emits one extra output. The
decoded aggregate becomes g_agg = h + Σints·1/(nα), and the extra output is
g_agg itself — which IS the new global shift (h' = h + mean Q = ĝ), so the
DIANA shift update costs zero extra HBM passes over the decode it fuses
with.

Canonical scalar vectors (f32, one per leaf — inv_nalpha varies per block
under the blockwise α rule; ``clip`` is the global-norm factor
min(1, c/||ĝ||) applied to the aggregate consumed by the update but NOT to
the shift output, matching the unfused route where the clip scales ĝ after
the shift state advanced):

    sgd   : [inv_nalpha, clip, lr, mu, wd]
    adamw : [inv_nalpha, clip, lr, b1, omb1, b2, omb2, eps, wd, bc1, bc2]

(omb1/omb2 = pre-rounded 1-b1 / 1-b2 — see optim.base.FUSED_SCALAR_TAIL for
why they are passed rather than recomputed in-kernel)

(see optim.base.FUSED_SCALAR_TAIL — optim owns the tail order, this module
owns the arithmetic.) The vector lives in SMEM; the blocks of the other
operands are chosen from a VMEM budget by ``repro.kernels.ops._block_for``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ---------------------------------------------------------------------------
# update arithmetic shared by the dense and packed kernels (operates on the
# in-register decoded aggregate; returns the output blocks to write)
# ---------------------------------------------------------------------------
def _apply_sgd(sc, g_agg, p, m):
    clip, lr, mu, wd = sc[1], sc[2], sc[3], sc[4]
    g = clip * g_agg + wd * p
    m_new = mu * m + g
    return p - lr * m_new, m_new


def _apply_adamw(sc, g_agg, p, m, v):
    clip, lr = sc[1], sc[2]
    b1, omb1, b2, omb2 = sc[3], sc[4], sc[5], sc[6]
    eps, wd, bc1, bc2 = sc[7], sc[8], sc[9], sc[10]
    g = clip * g_agg
    m_new = b1 * m + omb1 * g
    v_new = b2 * v + omb2 * g * g
    step = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    return p - lr * (step + wd * p), m_new, v_new


# ---------------------------------------------------------------------------
# dense kernels: one lane per coordinate (int8/int16/int32 widening cast)
# ---------------------------------------------------------------------------
def _sgd_kernel(sc_ref, ints_ref, p_ref, m_ref, *refs, has_shift):
    sc = sc_ref
    if has_shift:
        h_ref, po_ref, mo_ref, ho_ref = refs
    else:
        po_ref, mo_ref = refs
    p = p_ref[...].astype(jnp.float32)
    g_agg = ints_ref[...].astype(jnp.float32) * sc[0]
    if has_shift:
        g_agg = g_agg + h_ref[...].astype(jnp.float32)
        ho_ref[...] = g_agg.astype(ho_ref.dtype)
    p_new, m_new = _apply_sgd(sc, g_agg, p, m_ref[...].astype(jnp.float32))
    po_ref[...] = p_new.astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)


def _adamw_kernel(sc_ref, ints_ref, p_ref, m_ref, v_ref, *refs, has_shift):
    sc = sc_ref
    if has_shift:
        h_ref, po_ref, mo_ref, vo_ref, ho_ref = refs
    else:
        po_ref, mo_ref, vo_ref = refs
    p = p_ref[...].astype(jnp.float32)
    g_agg = ints_ref[...].astype(jnp.float32) * sc[0]
    if has_shift:
        g_agg = g_agg + h_ref[...].astype(jnp.float32)
        ho_ref[...] = g_agg.astype(ho_ref.dtype)
    p_new, m_new, v_new = _apply_adamw(
        sc, g_agg, p, m_ref[...].astype(jnp.float32),
        v_ref[...].astype(jnp.float32),
    )
    po_ref[...] = p_new.astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)
    vo_ref[...] = v_new.astype(vo_ref.dtype)


# ---------------------------------------------------------------------------
# packed kernels: k bias-shifted fields unpacked in-register per int32 word
# ---------------------------------------------------------------------------
def _unpack_sgd_kernel(sc_ref, w_ref, p_ref, m_ref, *refs,
                       k, bits, nlim, has_shift):
    sc = sc_ref
    if has_shift:
        h_ref, po_ref, mo_ref, ho_ref = refs
    else:
        po_ref, mo_ref = refs
    w = w_ref[...]  # (bm, bn) int32 transport words
    mask = (1 << bits) - 1
    for j in range(k):
        s = (((w >> (j * bits)) & mask) - nlim).astype(jnp.float32)
        g_agg = s * sc[0]
        if has_shift:
            g_agg = g_agg + h_ref[j].astype(jnp.float32)
            ho_ref[j, :, :] = g_agg.astype(ho_ref.dtype)
        p_new, m_new = _apply_sgd(
            sc, g_agg, p_ref[j].astype(jnp.float32),
            m_ref[j].astype(jnp.float32),
        )
        po_ref[j, :, :] = p_new.astype(po_ref.dtype)
        mo_ref[j, :, :] = m_new.astype(mo_ref.dtype)


def _unpack_adamw_kernel(sc_ref, w_ref, p_ref, m_ref, v_ref, *refs,
                         k, bits, nlim, has_shift):
    sc = sc_ref
    if has_shift:
        h_ref, po_ref, mo_ref, vo_ref, ho_ref = refs
    else:
        po_ref, mo_ref, vo_ref = refs
    w = w_ref[...]
    mask = (1 << bits) - 1
    for j in range(k):
        s = (((w >> (j * bits)) & mask) - nlim).astype(jnp.float32)
        g_agg = s * sc[0]
        if has_shift:
            g_agg = g_agg + h_ref[j].astype(jnp.float32)
            ho_ref[j, :, :] = g_agg.astype(ho_ref.dtype)
        p_new, m_new, v_new = _apply_adamw(
            sc, g_agg, p_ref[j].astype(jnp.float32),
            m_ref[j].astype(jnp.float32), v_ref[j].astype(jnp.float32),
        )
        po_ref[j, :, :] = p_new.astype(po_ref.dtype)
        mo_ref[j, :, :] = m_new.astype(mo_ref.dtype)
        vo_ref[j, :, :] = v_new.astype(vo_ref.dtype)


_DENSE_KERNELS = {"sgd": (_sgd_kernel, 1), "adamw": (_adamw_kernel, 2)}
_PACKED_KERNELS = {"sgd": (_unpack_sgd_kernel, 1),
                   "adamw": (_unpack_adamw_kernel, 2)}


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("kernel", "block", "interpret")
)
def fused_apply_2d(
    int_sum: jax.Array,  # (rows, cols) integer lanes (any int dtype)
    param: jax.Array,  # (rows, cols)
    opt: tuple,  # per-kernel f32 state tensors, each (rows, cols)
    scalars: jax.Array,  # canonical scalar vector (see module docstring)
    shift: jax.Array | None = None,  # (rows, cols) f32 global shift
    *,
    kernel: str = "sgd",
    block,
    interpret: bool = False,
):
    """Dense fused route: (p', opt', shift'|None) in one HBM pass."""
    body, n_state = _DENSE_KERNELS[kernel]
    assert len(opt) == n_state, (kernel, len(opt))
    rows, cols = int_sum.shape
    bm, bn = block
    assert rows % bm == 0 and cols % bn == 0
    grid = (rows // bm, cols // bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    has_shift = shift is not None
    inputs = [scalars.astype(jnp.float32), int_sum, param, *opt]
    out_shape = [jax.ShapeDtypeStruct(param.shape, param.dtype)]
    out_shape += [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in opt]
    if has_shift:
        inputs.append(shift)
        out_shape.append(jax.ShapeDtypeStruct(shift.shape, shift.dtype))
    outs = pl.pallas_call(
        functools.partial(body, has_shift=has_shift),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [spec] * (len(inputs) - 1),
        out_specs=tuple([spec] * len(out_shape)),
        out_shape=tuple(out_shape),
        interpret=interpret,
    )(*inputs)
    new_p, new_opt = outs[0], tuple(outs[1 : 1 + n_state])
    return new_p, new_opt, (outs[-1] if has_shift else None)


@functools.partial(
    jax.jit,
    static_argnames=("kernel", "bits", "nlim", "block", "interpret"),
)
def fused_unpack_apply_2d(
    words: jax.Array,  # (rows, cols) int32 packed words
    param: jax.Array,  # (k, rows, cols) image view
    opt: tuple,  # per-kernel f32 state tensors, each (k, rows, cols)
    scalars: jax.Array,
    shift: jax.Array | None = None,  # (k, rows, cols) f32 global shift
    *,
    kernel: str = "sgd",
    bits: int = 8,
    nlim: int = 0,  # accumulated bias n_summed * clip_limit
    block,
    interpret: bool = False,
):
    """Packed fused route: unpack in-register + update, one HBM pass."""
    body, n_state = _PACKED_KERNELS[kernel]
    assert len(opt) == n_state, (kernel, len(opt))
    rows, cols = words.shape
    k = 32 // bits
    bm, bn = block
    assert param.shape == (k, rows, cols)
    assert all(o.shape == param.shape for o in opt)
    assert rows % bm == 0 and cols % bn == 0
    grid = (rows // bm, cols // bn)
    wspec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    ispec = pl.BlockSpec((k, bm, bn), lambda i, j: (0, i, j))
    has_shift = shift is not None
    inputs = [scalars.astype(jnp.float32), words, param, *opt]
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), wspec]
    in_specs += [ispec] * (1 + len(opt))
    out_shape = [jax.ShapeDtypeStruct(param.shape, param.dtype)]
    out_shape += [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in opt]
    if has_shift:
        inputs.append(shift)
        in_specs.append(ispec)
        out_shape.append(jax.ShapeDtypeStruct(shift.shape, shift.dtype))
    outs = pl.pallas_call(
        functools.partial(body, k=k, bits=bits, nlim=nlim,
                          has_shift=has_shift),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple([ispec] * len(out_shape)),
        out_shape=tuple(out_shape),
        interpret=interpret,
    )(*inputs)
    new_p, new_opt = outs[0], tuple(outs[1 : 1 + n_state])
    return new_p, new_opt, (outs[-1] if has_shift else None)
