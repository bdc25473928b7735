"""Pallas kernel: per-row-block squared L2 norms (for blockwise α, Alg. 2).

Grid iterates over (block, tile-within-block); the f32 accumulator of block
b is element b of the whole (nblocks,) output, held in SMEM for the entire
grid (a scalar store is only legal in SMEM, and a (1, 1) VMEM block would
break the (8, 128) tiling). TPU grid execution is sequential, so
read-modify-write accumulation across grid steps is well-defined."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, o_ref):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[b] = jnp.float32(0.0)

    x = x_ref[...].astype(jnp.float32)
    o_ref[b] += jnp.sum(x * x)


@functools.partial(jax.jit, static_argnames=("block_rows", "tile", "interpret"))
def block_norms_2d(
    x: jax.Array,
    *,
    block_rows: int,
    tile,
    interpret: bool = False,
) -> jax.Array:
    """x: (rows, cols); rows % block_rows == 0; returns (rows//block_rows,)
    squared norms. block_rows % tile[0] == 0 and cols % tile[1] == 0."""
    rows, cols = x.shape
    bm, bn = tile
    assert rows % block_rows == 0 and block_rows % bm == 0 and cols % bn == 0
    nblocks = rows // block_rows
    tiles_per_block = (block_rows // bm) * (cols // bn)
    tb_rows = block_rows // bm

    def x_map(b, j):
        # j enumerates tiles inside block b, row-major
        return (b * tb_rows + j // (cols // bn), j % (cols // bn))

    return pl.pallas_call(
        _kernel,
        grid=(nblocks, tiles_per_block),
        in_specs=[pl.BlockSpec((bm, bn), x_map)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((nblocks,), jnp.float32),
        interpret=interpret,
    )(x)
