"""Public jit'd wrappers around the Pallas kernels.

Handles arbitrary tensor shapes by flattening to a padded row-major 2-D view
(pad-at-end keeps the kernel's flat element counter identical to the
oracle's logical index, so stochastic rounding is bit-exact vs ref.py).

The fused unpack+update route (``fused_unpack_apply``) first tries the
leaf's own rows instead. The canonical word layout puts field j of word w
at flat[j·m + w]; for a leaf of shape (..., C) with rows = prod(shape[:-1])
and rows % k == 0, m = rows/k·C, so chunk j is exactly rows
[j·rows/k, (j+1)·rows/k) of the leaf's (rows, C) view and
``t.reshape(k, rows // k, C)`` is the kernel's image view. With rows/k a
multiple of 8 that reshape only merges and splits major dims at (8, 128)
tile boundaries: a bitcast on the TPU, with no pad, slice or relayout of
the f32 tensors either way. Leaves without such a view (1-D leaves, rows
not a multiple of 8k, or no block that fits) take the padded view.

On non-TPU backends the kernels run under ``interpret=True`` (the kernel body
executed op-by-op on CPU) — the TARGET remains TPU Mosaic; CPU execution is
for validation only.
"""
from __future__ import annotations

import functools
import math

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
    holds. With ``_native_block`` below, the one place any kernel's block
    is chosen."""
    if size < _SMALL:
        return (8, 128)
    bm = _MAX_ROWS
    while bm > 8 and 2 * bm * _LANES * stream_bytes > VMEM_BUDGET:
        bm //= 2
    return (bm, _LANES)


def _native_block(rows: int, cols: int, stream_bytes: int):
    """Block of a (rows, cols) view that is not padded to the block grid:
    bm a multiple of 8 that divides rows, bn a multiple of 128 that divides
    cols or cols itself, 2·bm·bn·stream_bytes within VMEM_BUDGET; the most
    positions, then the widest. None where no such block fits."""
    if rows % 8:
        return None
    best = None
    widths = {cols} | {b for b in range(128, cols, 128) if cols % b == 0}
    for bn in widths:
        bm = min(rows, VMEM_BUDGET // (2 * bn * stream_bytes)) // 8 * 8
        while bm >= 8 and rows % bm:
            bm -= 8
        if bm >= 8 and (best is None or (bm * bn, bn) > (best[0] * best[1],
                                                         best[1])):
            best = (bm, bn)
    return best


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


def _fused_stream_bytes(k: int, n_tensors: int) -> int:
    """Bytes a word position of the packed fused kernel moves: the word in,
    and k f32 planes of each of its n_tensors tensors (param, state, shift)
    in and out."""
    return 4 + 2 * 4 * k * n_tensors


def _native_view(shape, k: int, stream_bytes: int):
    """(rows // k, C, block) of a leaf's native row-chunk view (module
    docstring), or None where the leaf takes the padded view."""
    if len(shape) < 2:
        return None
    rows, cols = math.prod(shape[:-1]), shape[-1]
    if rows % k:
        return None
    block = _native_block(rows // k, cols, stream_bytes)
    return None if block is None else (rows // k, cols, block)


def fused_view(shape, *, bits: int, n_tensors: int = 2) -> str:
    """The view ``fused_unpack_apply`` takes of a leaf of `shape` on a
    `bits`-bit packed wire, its kernel reading `n_tensors` f32 tensors
    (param, optimizer state, shift): "native" (the leaf's own rows, no
    copy) or "padded"."""
    k = 32 // bits
    native = _native_view(tuple(shape), k, _fused_stream_bytes(k, n_tensors))
    return "padded" if native is None else "native"


def _native_views(words, shape, k: int, stream_bytes: int):
    """(words 2-D, view, unview, block) of the native row-chunk view: the
    words as (rows/k, C), each f32 tensor as (k, rows/k, C)."""
    rows, cols, block = _native_view(shape, k, stream_bytes)

    def view(t):
        return t.astype(jnp.float32).reshape(k, rows, cols)

    def unview(t, dt):
        return t.reshape(shape).astype(dt)

    return words.reshape(rows, cols), view, unview, block


def _padded_views(words, shape, k: int, stream_bytes: int):
    """(words 2-D, view, unview, block) of the padded image view: every
    tensor flattened, padded to k·m and to whole word blocks, and sliced
    back after."""
    m, d = words.size, math.prod(shape)
    block = _block_for(m, stream_bytes)

    def view(t):
        flat = t.reshape(-1).astype(jnp.float32)
        return _image_view(jnp.pad(flat, (0, k * m - d)), k, m, block)

    def unview(t, dt):
        flat = t.reshape(k, -1)[:, :m].reshape(-1)[:d]
        return flat.reshape(shape).astype(dt)

    return _to_2d(words.reshape(-1), block), view, unview, block


def _leaf_views(words, shape, k: int, stream_bytes: int):
    """The native row-chunk view where the leaf's shape has one, else the
    padded image view."""
    native = _native_view(shape, k, stream_bytes) is not None
    views = _native_views if native else _padded_views
    return views(words, shape, k, stream_bytes)


def _fused_unpack_in(views, words, param, opt, scalars, shift=None, *,
                     kernel: str, bits: int, n_summed: int,
                     interpret: bool | None = None):
    """The packed fused kernel over the view that `views` (``_leaf_views``,
    ``_native_views`` or ``_padded_views``) builds."""
    interpret = _interpret_default() if interpret is None else interpret
    k = 32 // bits
    nlim = n_summed * _ic.clip_limit(bits, n_summed)
    assert words.size == -(-param.size // k), (words.size, param.size, k)
    stream_bytes = _fused_stream_bytes(k, 1 + len(opt) + (shift is not None))
    w2, view, unview, block = views(words, param.shape, k, stream_bytes)
    po3, opt3, ho3 = _fu.fused_unpack_apply_2d(
        w2, view(param), tuple(view(o) for o in opt), scalars,
        None if shift is None else view(shift),
        kernel=kernel, bits=bits, nlim=nlim, block=block,
        interpret=interpret,
    )
    return (
        unview(po3, param.dtype),
        tuple(unview(o3, o.dtype) for o3, o in zip(opt3, opt)),
        None if ho3 is None else unview(ho3, shift.dtype),
    )


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
    HBM). Returns (param', opt', shift'|None).

    The leaf's shape alone chooses the view (``fused_view``). Where the
    leaf has a native row-chunk view (module docstring), the words are read
    as (rows/k, C) and each f32 tensor as (k, rows/k, C): reshapes of the
    leaf's own layout, bitcasts on the TPU. Otherwise every tensor is
    flattened, padded to whole word blocks and sliced back after. Both
    views hand the kernel the same elements at the same word positions, so
    their outputs are bit-identical."""
    return _fused_unpack_in(
        _leaf_views, words, param, opt, scalars, shift,
        kernel=kernel, bits=bits, n_summed=n_summed, interpret=interpret,
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
