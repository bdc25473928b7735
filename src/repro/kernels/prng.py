"""Counter-based PRNG shared by kernels and their oracles.

fmix32 (MurmurHash3 finalizer) over (element-counter ^ seed): statistically
solid for rounding noise, stateless, and expressible in pure jnp uint32 ops —
so the Pallas kernel, the ref.py oracle and the XLA rounding route
(core/rounding.py) produce *identical* bits, enabling bit-exact validation
of the stochastic rounding path on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# numpy scalars embed as literals inside Pallas kernels (jnp arrays would be
# captured constants, which pallas_call rejects)
_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


def fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """MurmurHash3 32-bit finalizer; input/output uint32."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _C1
    x = x ^ (x >> 13)
    x = x * _C2
    x = x ^ (x >> 16)
    return x


def uniform_from_counter(counter: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """U[0,1) floats from an integer counter grid and an int32 seed."""
    h = fmix32(counter.astype(jnp.uint32) * _GOLDEN + seed.astype(jnp.uint32))
    # 24 high-quality mantissa bits -> [0, 1). The top 24 bits fit a
    # non-negative int32 exactly, and int32 -> f32 is the conversion the TPU
    # kernel compiler accepts (it has no uint32 -> f32 cast); the value is
    # the same either way, so the kernel and the oracle stay bit-identical.
    return (h >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) * np.float32(
        2.0**-24
    )


def seed_from_key(key: jax.Array) -> jax.Array:
    """The int32 seed a PRNG key selects for :func:`uniform_from_counter`."""
    return jax.random.bits(key, (), jnp.uint32).astype(jnp.int32)


def flat_index(shape) -> jnp.ndarray:
    """uint32 row-major flat index of every element of `shape` — the counter
    the kernels and the oracle use — built from per-axis iotas, so no large
    1-D array is ever reshaped."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in reversed(range(len(shape))):
        idx = idx + lax.broadcasted_iota(jnp.uint32, shape, ax) * np.uint32(stride)
        stride *= int(shape[ax])
    return idx
