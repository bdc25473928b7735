"""Public jit'd wrappers around the Pallas kernels.

Handles arbitrary tensor shapes by flattening to a padded row-major 2-D view
(pad-at-end keeps the kernel's flat element counter identical to the
oracle's logical index, so stochastic rounding is bit-exact vs ref.py).

On non-TPU backends the kernels run under ``interpret=True`` (the kernel body
executed op-by-op on CPU) — the TARGET remains TPU Mosaic; CPU execution is
for validation only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import block_norms as _bn
from repro.kernels import fused_update as _fu
from repro.kernels import int_compress as _ic
from repro.kernels import wire_pack as _wp
from repro.kernels.prng import seed_from_key


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# Every grid step double-buffers each input and output block in VMEM, so a
# (bm, bn) block costs 2·bm·bn·B bytes, where B is what ONE position of the
# 2-D view moves over all of the kernel's streams (each of the k planes of
# an image-view tensor counts). Half of the TPU v5e's 16 MiB default scoped
# VMEM limit goes to these buffers; the other half stays free for in-kernel
# temporaries.
VMEM_BUDGET = 8 * 2**20
_SMALL = 2**18
_LANES = 1024
_MAX_ROWS = 256


def _block_for(size: int, stream_bytes: int):
    """Block of a 2-D view of `size` positions whose kernel moves
    `stream_bytes` bytes per position: (8, 128) for small arrays, else 1024
    lanes by the most rows (a power of two in [8, 256]) that VMEM_BUDGET
    holds. The one place any kernel's block is chosen."""
    if size < _SMALL:
        return (8, 128)
    bm = _MAX_ROWS
    while bm > 8 and 2 * bm * _LANES * stream_bytes > VMEM_BUDGET:
        bm //= 2
    return (bm, _LANES)


def _to_2d(flat: jax.Array, block):
    bm, bn = block
    chunk = bm * bn
    padded = (flat.size + chunk - 1) // chunk * chunk
    flat = jnp.pad(flat, (0, padded - flat.size))
    return flat.reshape(padded // bn, bn)


@functools.partial(
    jax.jit, static_argnames=("n_workers", "bits", "stochastic", "interpret")
)
def int_compress(
    x: jax.Array,
    alpha: jax.Array,
    key: jax.Array,
    *,
    n_workers: int,
    bits: int = 32,
    stochastic: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Int(α∘x) clipped for the n-worker sum — kernel-accelerated encode."""
    interpret = _interpret_default() if interpret is None else interpret
    seed = seed_from_key(key)
    shape = x.shape
    block = _block_for(x.size, 4 + 4)
    x2 = _to_2d(x.reshape(-1).astype(jnp.float32), block)
    out = _ic.int_compress_2d(
        x2,
        alpha,
        seed,
        n_workers=n_workers,
        bits=bits,
        stochastic=stochastic,
        block=block,
        interpret=interpret,
    )
    return out.reshape(-1)[: x.size].reshape(shape)


def _image_view(flat: jax.Array, k: int, m: int, block):
    """(k·m,) chunk-major flat image -> (k, rows, bn) view aligned to the
    word-block grid (words padded along the word axis only, so the canonical
    word layout word[w] <- flat[j·m + w] is preserved)."""
    bm, bn = block
    chunk = bm * bn
    mp = (m + chunk - 1) // chunk * chunk
    ch = jnp.pad(flat.reshape(k, m), ((0, 0), (0, mp - m)))
    return ch.reshape(k, mp // bn, bn)


@functools.partial(
    jax.jit, static_argnames=("bits", "n_workers", "interpret")
)
def pack_words(
    ints: jax.Array,
    *,
    bits: int,
    n_workers: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Bit-pack a clipped integer image into int32 transport words (flat,
    length ceil(size / (32//bits))) — kernel-accelerated PackedInt.pack."""
    interpret = _interpret_default() if interpret is None else interpret
    k = 32 // bits
    lim = _ic.clip_limit(bits, n_workers)
    flat = ints.reshape(-1).astype(jnp.int32)
    m = -(-flat.size // k)
    flat = jnp.pad(flat, (0, k * m - flat.size))
    block = _block_for(m, 4 * k + 4)
    x3 = _image_view(flat, k, m, block)
    w2 = _wp.pack_words_2d(
        x3, bits=bits, lim=lim, block=block, interpret=interpret
    )
    return w2.reshape(-1)[:m]


@functools.partial(
    jax.jit, static_argnames=("shape", "bits", "n_summed", "interpret")
)
def unpack_words(
    words: jax.Array,
    shape,
    *,
    bits: int,
    n_summed: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Summed transport words -> summed integer image of `shape` (int32)."""
    interpret = _interpret_default() if interpret is None else interpret
    k = 32 // bits
    nlim = n_summed * _ic.clip_limit(bits, n_summed)
    size = 1
    for s in shape:
        size *= int(s)
    m = words.size
    assert m == -(-size // k), (m, size, k)
    block = _block_for(m, 4 + 4 * k)
    w2 = _to_2d(words.reshape(-1), block)
    out3 = _wp.unpack_words_2d(
        w2, bits=bits, nlim=nlim, block=block, interpret=interpret
    )
    flat = out3.reshape(k, -1)[:, :m].reshape(-1)[:size]
    return flat.reshape(shape)


@functools.partial(
    jax.jit,
    static_argnames=("kernel", "bits", "n_summed", "interpret"),
)
def fused_unpack_apply(
    words: jax.Array,
    param: jax.Array,
    opt: tuple,  # per-kernel f32 state tensors, param-shaped
    scalars: jax.Array,  # canonical vector (see kernels/fused_update.py)
    shift: jax.Array | None = None,
    *,
    kernel: str = "sgd",
    bits: int,
    n_summed: int,
    interpret: bool | None = None,
):
    """PackedInt fused route, any optimizer kernel: the update consumes the
    bit-packed transport words directly (no unpacked integer image ever hits
    HBM). Returns (param', opt', shift'|None)."""
    interpret = _interpret_default() if interpret is None else interpret
    k = 32 // bits
    nlim = n_summed * _ic.clip_limit(bits, n_summed)
    shape, d = param.shape, param.size
    m = words.size
    assert m == -(-d // k), (m, d, k)
    # words in + k f32 planes of param, state and shift, each in and out
    n_tensors = 1 + len(opt) + (shift is not None)
    block = _block_for(m, 4 + 2 * 4 * k * n_tensors)
    w2 = _to_2d(words.reshape(-1), block)

    def view(t):
        flat = t.reshape(-1).astype(jnp.float32)
        return _image_view(jnp.pad(flat, (0, k * m - d)), k, m, block)

    po3, opt3, ho3 = _fu.fused_unpack_apply_2d(
        w2, view(param), tuple(view(o) for o in opt), scalars,
        None if shift is None else view(shift),
        kernel=kernel, bits=bits, nlim=nlim, block=block,
        interpret=interpret,
    )

    def unview(t, dt):
        flat = t.reshape(k, -1)[:, :m].reshape(-1)[:d]
        return flat.reshape(shape).astype(dt)

    return (
        unview(po3, param.dtype),
        tuple(unview(o3, o.dtype) for o3, o in zip(opt3, opt)),
        None if ho3 is None else unview(ho3, shift.dtype),
    )


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"))
def fused_apply(
    int_sum: jax.Array,
    param: jax.Array,
    opt: tuple,
    scalars: jax.Array,
    shift: jax.Array | None = None,
    *,
    kernel: str = "sgd",
    interpret: bool | None = None,
):
    """Dense fused route, any optimizer kernel: optimizer step fused with
    integer dequantization. Returns (param', opt', shift'|None)."""
    interpret = _interpret_default() if interpret is None else interpret
    shape = param.shape
    # integer lanes in + f32 param, state and shift, each in and out
    n_tensors = 1 + len(opt) + (shift is not None)
    block = _block_for(param.size, int_sum.dtype.itemsize + 2 * 4 * n_tensors)
    to2 = lambda t: _to_2d(t.reshape(-1).astype(jnp.float32), block)
    po, opt2, ho = _fu.fused_apply_2d(
        _to_2d(int_sum.reshape(-1), block), to2(param),
        tuple(to2(o) for o in opt), scalars,
        None if shift is None else to2(shift),
        kernel=kernel, block=block, interpret=interpret,
    )
    unpad = lambda a, dt: a.reshape(-1)[: param.size].reshape(shape).astype(dt)
    return (
        unpad(po, param.dtype),
        tuple(unpad(o2, o.dtype) for o2, o in zip(opt2, opt)),
        None if ho is None else unpad(ho, shift.dtype),
    )


def _sgd_scalars(inv_nalpha, lr, mu, wd):
    return jnp.stack(
        [
            jnp.asarray(inv_nalpha, jnp.float32),
            jnp.float32(1.0),  # clip
            jnp.asarray(lr, jnp.float32),
            jnp.asarray(mu, jnp.float32),
            jnp.asarray(wd, jnp.float32),
        ]
    )


@functools.partial(
    jax.jit, static_argnames=("bits", "n_summed", "interpret")
)
def fused_unpack_update(
    words: jax.Array,
    param: jax.Array,
    mom: jax.Array,
    inv_nalpha: jax.Array,
    lr: jax.Array,
    mu: jax.Array,
    wd: jax.Array,
    *,
    bits: int,
    n_summed: int,
    interpret: bool | None = None,
):
    """Momentum-SGD shorthand over :func:`fused_unpack_apply`."""
    p, (m,), _ = fused_unpack_apply(
        words, param, (mom,), _sgd_scalars(inv_nalpha, lr, mu, wd),
        kernel="sgd", bits=bits, n_summed=n_summed, interpret=interpret,
    )
    return p, m


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_update(
    int_sum: jax.Array,
    param: jax.Array,
    mom: jax.Array,
    inv_nalpha: jax.Array,
    lr: jax.Array,
    mu: jax.Array,
    wd: jax.Array,
    *,
    interpret: bool | None = None,
):
    """p', m' = sgd-with-momentum step fused with integer dequantization."""
    p, (m,), _ = fused_apply(
        int_sum, param, (mom,), _sgd_scalars(inv_nalpha, lr, mu, wd),
        kernel="sgd", interpret=interpret,
    )
    return p, m


@functools.partial(jax.jit, static_argnames=("interpret",))
def sq_norm(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """||x||² via the block-norms reduction kernel (single block)."""
    interpret = _interpret_default() if interpret is None else interpret
    block = _block_for(x.size, 4)
    x2 = _to_2d(x.reshape(-1).astype(jnp.float32), block)
    out = _bn.block_norms_2d(
        x2, block_rows=x2.shape[0], tile=(block[0], x2.shape[1]), interpret=interpret
    )
    return out[0]


@functools.partial(jax.jit, static_argnames=("nblocks", "interpret"))
def block_sq_norms(x: jax.Array, nblocks: int, *, interpret: bool | None = None):
    """Squared norms of `nblocks` equal contiguous chunks of flat(x)."""
    interpret = _interpret_default() if interpret is None else interpret
    flat = x.reshape(-1).astype(jnp.float32)
    bm, bn = (8, 128)
    per = (flat.size + nblocks - 1) // nblocks
    per = (per + bm * bn - 1) // (bm * bn) * (bm * bn)
    flat = jnp.pad(flat, (0, per * nblocks - flat.size))
    x2 = flat.reshape(per * nblocks // bn, bn)
    return _bn.block_norms_2d(
        x2, block_rows=per // bn, tile=(bm, bn), interpret=interpret
    )
