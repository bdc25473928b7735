"""Randomized and deterministic integer rounding — the paper's Int operator.

    Int(t) = floor(t) + 1  with prob  t - floor(t)
             floor(t)      otherwise                       (paper §2)

Properties (Lemma 1, verified by tests/test_rounding.py):
    E[Int(t)] = t
    E[(Int(t) - t)^2] <= 1/4      (Bernoulli variance bound)

The float-domain quantizer is  Q(x) = (1/α) ∘ Int(α ∘ x)  (eq. 2). In the
distributed algorithm the *integer* image Int(α ∘ x) is what crosses the wire;
Q is only materialized after aggregation.

Overflow safety: the paper clips local integers so that the *sum over n
workers* fits the wire dtype (int8 or int32): |Int(α g_i)| <= (2^(b-1)-1)/n.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.prng import flat_index, seed_from_key, uniform_from_counter

# The §5.1 range/limit primitives are owned by the wire subsystem (they
# define what the transport can carry); re-exported here for the scalar-lane
# reference path and back-compat. repro.wire deliberately has no module-level
# core imports, so this direction is cycle-free.
from repro.wire.base import (  # noqa: F401  (re-exports)
    _INT_RANGE,
    WireRangeError,
    clip_limit as _wire_clip_limit,
)


def stochastic_round(x: jax.Array, key: jax.Array) -> jax.Array:
    """Randomized rounding to the nearest integers, unbiased (float dtype out).

    The uniforms come from the counter-based PRNG of the Pallas encode kernel
    (:mod:`repro.kernels.prng`, counter = flat element index, seed drawn from
    `key`), so this route and the kernel round identically for one key. It
    is also a few integer ops per element: the TPU compiler spent minutes on
    ``jax.random.uniform``'s threefry over a gradient of a billion-parameter
    model, and seconds on this."""
    x = x.astype(jnp.float32)
    lo = jnp.floor(x)
    p = x - lo
    u = uniform_from_counter(flat_index(x.shape), seed_from_key(key))
    return lo + (u < p).astype(jnp.float32)


def deterministic_round(x: jax.Array) -> jax.Array:
    """Round-half-even (`torch.round` analogue) — the IntSGD (Determ.) variant."""
    return jnp.round(x.astype(jnp.float32))


def int_round(
    x: jax.Array,
    key: jax.Array | None,
    *,
    stochastic: bool = True,
) -> jax.Array:
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        return stochastic_round(x, key)
    return deterministic_round(x)


def clip_limit(*, n_workers: int, bits: int) -> int:
    """The §5.1 clip limit: largest |v| such that the n-worker sum fits
    `bits`. Raises :class:`WireRangeError` when the limit degenerates to 0
    (the n-worker sum cannot be represented at all) instead of silently
    zeroing every gradient coordinate. Canonical impl: repro.wire.base."""
    return _wire_clip_limit(n_workers=n_workers, bits=bits)


def clip_for_wire(ints: jax.Array, *, n_workers: int, bits: int) -> jax.Array:
    """Clip local integers so the n-worker sum fits the wire dtype (paper §5.1)."""
    lim = clip_limit(n_workers=n_workers, bits=bits)
    return jnp.clip(ints, -lim, lim)


def wire_dtype(bits: int):
    """Narrowest native integer lane that holds one `bits`-wide value."""
    return {4: jnp.int8, 8: jnp.int8, 16: jnp.int16, 32: jnp.int32}[bits]


def encode(
    x: jax.Array,
    alpha: jax.Array,
    key: jax.Array | None,
    *,
    n_workers: int,
    bits: int = 32,
    stochastic: bool = True,
) -> jax.Array:
    """x -> Int(α ∘ x), clipped to the wire range, in the wire integer dtype.

    Contract: the result is transported in the NARROWEST native lane that
    holds one `bits`-wide value (int8 for bits<=8, int16, int32 — see
    :func:`wire_dtype`), and the §5.1 clip guarantees the n-worker SUM still
    fits `bits`, so an all-reduce of the returned array is overflow-safe in
    its own lane dtype. This is the reference scalar-lane transport; the
    bit-packed transport (sub-words coded into int32 lanes) lives in
    :mod:`repro.wire` and shares this clip.
    """
    r = int_round(x.astype(jnp.float32) * alpha, key, stochastic=stochastic)
    r = clip_for_wire(r, n_workers=n_workers, bits=bits)
    return r.astype(wire_dtype(bits))


def decode(ints: jax.Array, alpha: jax.Array, *, n_workers: int) -> jax.Array:
    """Aggregated integers -> gradient estimate: (1/(n α)) ∘ Σ_i Int(α g_i)."""
    return ints.astype(jnp.float32) / (n_workers * alpha)
