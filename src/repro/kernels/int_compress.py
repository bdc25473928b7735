"""Pallas kernel: fused scale + stochastic/deterministic round + clip → int32.

One HBM read of the f32 gradient tile, one HBM write of the int32 image —
the entire Int(α∘g) operator of the paper in a single VMEM pass.

Tiling: 2-D grid over a (rows, cols) view; blocks are (BM, BN) with BN a
multiple of 128 (lane width) and BM a multiple of 8 (sublane, f32), chosen
from a VMEM budget by ``repro.kernels.ops._block_for``. The α and seed
scalars live in SMEM (a vector load from an unplaced memory space is
illegal on the TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.prng import uniform_from_counter

_INT_LIM = {4: 7, 8: 127, 16: 32767, 32: 2147483647}


def clip_limit(bits: int, n_workers: int) -> int:
    """§5.1 clip limit as the kernels see it (single kernel-layer copy;
    the wire layer raises its typed WireRangeError before reaching here)."""
    lim = _INT_LIM[bits] // max(n_workers, 1)
    if lim == 0:
        raise ValueError(
            f"int{bits} wire cannot carry a sum over {n_workers} workers "
            "(clip limit degenerates to 0; widen the wire)"
        )
    return lim


def _kernel(alpha_ref, seed_ref, x_ref, o_ref, *, lim, stochastic, ncols, block):
    bm, bn = block
    i = pl.program_id(0)
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    scaled = x * alpha_ref[0]
    if stochastic:
        # global flat element counter (row-major over the padded 2-D view):
        # identical to the oracle's jnp.arange counter.
        row = lax.broadcasted_iota(jnp.uint32, (bm, bn), 0) + jnp.uint32(i * bm)
        col = lax.broadcasted_iota(jnp.uint32, (bm, bn), 1) + jnp.uint32(j * bn)
        counter = row * jnp.uint32(ncols) + col
        u = uniform_from_counter(counter, seed_ref[0])
        lo = jnp.floor(scaled)
        r = lo + (u < (scaled - lo)).astype(jnp.float32)
    else:
        r = jnp.round(scaled)
    r = jnp.clip(r, -float(lim), float(lim))
    o_ref[...] = r.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("n_workers", "bits", "stochastic", "block", "interpret"),
)
def int_compress_2d(
    x: jax.Array,
    alpha: jax.Array,
    seed: jax.Array,
    *,
    n_workers: int,
    bits: int = 32,
    stochastic: bool = True,
    block,
    interpret: bool = False,
) -> jax.Array:
    """x: (rows, cols) f32, rows % block[0] == 0, cols % block[1] == 0."""
    rows, cols = x.shape
    bm, bn = block
    assert rows % bm == 0 and cols % bn == 0, (x.shape, block)
    lim = clip_limit(bits, n_workers)
    grid = (rows // bm, cols // bn)
    return pl.pallas_call(
        functools.partial(
            _kernel, lim=lim, stochastic=stochastic, ncols=cols, block=block
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # alpha (scalar)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.int32),
        interpret=interpret,
    )(
        alpha.reshape(1).astype(jnp.float32),
        seed.reshape(1).astype(jnp.int32),
        x,
    )
