"""Pallas kernels: bit-pack / unpack PackedInt transport words.

Mirrors ``int_compress_2d``'s tiling: a 2-D grid over a (rows, cols) view of
the WORD array, blocks (BM, BN) with BN a multiple of 128 and BM a multiple
of 8. The integer image rides along as a (k, rows, cols) view — field j of
word (r, c) is image element (j, r, c) — so each grid step is one VMEM pass:
read k sub-blocks + write one word block (pack), or the reverse (unpack).

Field arithmetic is plain int32 with wrap-around (mod 2^32) semantics:
pack adds bias-shifted fields (never carrying across field boundaries by the
§5.1 clip — see repro/wire/packed.py for the invariant), unpack extracts
with arithmetic-shift + mask (sign-extension only touches masked-off bits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

def _pack_kernel(x_ref, o_ref, *, k, bits, lim):
    x = x_ref[...]  # (k, bm, bn) int32
    word = x[0] + lim
    for j in range(1, k):
        word = word + ((x[j] + lim) << (j * bits))
    o_ref[...] = word


def _unpack_kernel(w_ref, o_ref, *, k, bits, nlim):
    w = w_ref[...]  # (bm, bn) int32
    mask = (1 << bits) - 1
    for j in range(k):
        o_ref[j, :, :] = ((w >> (j * bits)) & mask) - nlim


@functools.partial(
    jax.jit, static_argnames=("bits", "lim", "block", "interpret")
)
def pack_words_2d(
    x: jax.Array,  # (k, rows, cols) int32 image view
    *,
    bits: int,
    lim: int,
    block,
    interpret: bool = False,
) -> jax.Array:
    k, rows, cols = x.shape
    bm, bn = block
    assert k == 32 // bits and rows % bm == 0 and cols % bn == 0, (x.shape, block)
    grid = (rows // bm, cols // bn)
    return pl.pallas_call(
        functools.partial(_pack_kernel, k=k, bits=bits, lim=lim),
        grid=grid,
        in_specs=[pl.BlockSpec((k, bm, bn), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.int32),
        interpret=interpret,
    )(x)


@functools.partial(
    jax.jit, static_argnames=("bits", "nlim", "block", "interpret")
)
def unpack_words_2d(
    words: jax.Array,  # (rows, cols) int32 transport words
    *,
    bits: int,
    nlim: int,  # accumulated bias n_summed * clip_limit
    block,
    interpret: bool = False,
) -> jax.Array:
    rows, cols = words.shape
    k = 32 // bits
    bm, bn = block
    assert rows % bm == 0 and cols % bn == 0, (words.shape, block)
    grid = (rows // bm, cols // bn)
    return pl.pallas_call(
        functools.partial(_unpack_kernel, k=k, bits=bits, nlim=nlim),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((k, bm, bn), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((k, rows, cols), jnp.int32),
        interpret=interpret,
    )(words)
