"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors its kernel's semantics exactly, including the
counter-based PRNG, so tests can assert bit-exact (integer outputs) or
allclose (float outputs) equality across shape/dtype sweeps.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.prng import uniform_from_counter

_INT_LIM = {4: 7, 8: 127, 16: 32767, 32: 2147483647}


def int_compress_ref(
    x: jnp.ndarray,
    alpha: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    n_workers: int,
    bits: int = 32,
    stochastic: bool = True,
) -> jnp.ndarray:
    """Int(α∘x), clipped so the n-worker sum fits `bits`, as int32.

    Counter = flat element index (row-major over the padded 2-D view used by
    the kernel — for the oracle we use the logical flat index, and ops.py
    guarantees the kernel sees the same flat layout).
    """
    orig_shape = x.shape
    xf = x.astype(jnp.float32).reshape(-1)
    scaled = xf * alpha.astype(jnp.float32)
    if stochastic:
        counter = jnp.arange(xf.size, dtype=jnp.uint32)
        u = uniform_from_counter(counter, seed)
        lo = jnp.floor(scaled)
        r = lo + (u < (scaled - lo)).astype(jnp.float32)
    else:
        r = jnp.round(scaled)
    lim = _INT_LIM[bits] // max(n_workers, 1)
    r = jnp.clip(r, -lim, lim)
    return r.astype(jnp.int32).reshape(orig_shape)


def pack_words_ref(
    ints: jnp.ndarray, *, bits: int, n_workers: int
) -> jnp.ndarray:
    """Canonical PackedInt word layout, computed in uint32 mul/add arithmetic
    (deliberately NOT shifts, so the kernels are checked against an
    independent formulation): word[w] = Σ_j (flat[j·m + w] + lim) · 2^(j·b)
    mod 2^32, with m = ceil(size/k), k = 32//bits."""
    k = 32 // bits
    lim = _INT_LIM[bits] // max(n_workers, 1)
    flat = ints.reshape(-1).astype(jnp.int32)
    m = -(-flat.size // k)
    flat = jnp.pad(flat, (0, k * m - flat.size))
    word = jnp.zeros((m,), jnp.uint32)
    for j in range(k):
        chunk = flat[j * m : (j + 1) * m]
        word = word + (chunk + lim).astype(jnp.uint32) * jnp.uint32(
            2 ** (j * bits)
        )
    return word.astype(jnp.int32)


def unpack_words_ref(
    words: jnp.ndarray, shape, *, bits: int, n_summed: int
) -> jnp.ndarray:
    """Inverse of pack_words_ref after an n_summed-worker wrap-around sum:
    field j = (word // 2^(j·b)) mod 2^b − n_summed·lim (uint32 div/mod)."""
    k = 32 // bits
    lim = _INT_LIM[bits] // max(n_summed, 1)
    size = 1
    for s in shape:
        size *= int(s)
    u = words.reshape(-1).astype(jnp.uint32)
    fields = [
        (u // jnp.uint32(2 ** (j * bits)) % jnp.uint32(2**bits)).astype(
            jnp.int32
        )
        - n_summed * lim
        for j in range(k)
    ]
    return jnp.concatenate(fields)[:size].reshape(shape)


def fused_unpack_update_ref(
    words: jnp.ndarray,
    param: jnp.ndarray,
    mom: jnp.ndarray,
    *,
    bits: int,
    n_summed: int,
    inv_nalpha: jnp.ndarray,
    lr: jnp.ndarray,
    mu: jnp.ndarray,
    wd: jnp.ndarray,
):
    """unpack_words_ref composed with fused_update_ref."""
    int_sum = unpack_words_ref(
        words, param.shape, bits=bits, n_summed=n_summed
    )
    return fused_update_ref(
        int_sum, param, mom, inv_nalpha=inv_nalpha, lr=lr, mu=mu, wd=wd
    )


def fused_update_ref(
    int_sum: jnp.ndarray,
    param: jnp.ndarray,
    mom: jnp.ndarray,
    *,
    inv_nalpha: jnp.ndarray,
    lr: jnp.ndarray,
    mu: jnp.ndarray,
    wd: jnp.ndarray,
):
    """Dequantize + weight decay + momentum + SGD step (torch semantics)."""
    g = int_sum.astype(jnp.float32) * inv_nalpha + wd * param.astype(jnp.float32)
    new_m = mu * mom.astype(jnp.float32) + g
    new_p = param.astype(jnp.float32) - lr * new_m
    return new_p.astype(param.dtype), new_m.astype(mom.dtype)


def fused_adamw_ref(
    int_sum: jnp.ndarray,
    param: jnp.ndarray,
    mu: jnp.ndarray,
    nu: jnp.ndarray,
    *,
    inv_nalpha,
    lr,
    b1,
    b2,
    eps,
    wd,
    bc1,
    bc2,
    clip=1.0,
    shift: jnp.ndarray | None = None,
):
    """Dequantize (+ global shift) + bias-corrected AdamW step.

    Mirrors the fused kernels' arithmetic: g_agg = shift + Σints/(nα) is
    what the new global shift would be (IntDIANA); the update consumes
    clip·g_agg. Returns (p', mu', nu', g_agg)."""
    g_agg = int_sum.astype(jnp.float32) * inv_nalpha
    if shift is not None:
        g_agg = g_agg + shift.astype(jnp.float32)
    g = clip * g_agg
    p32 = param.astype(jnp.float32)
    new_m = b1 * mu.astype(jnp.float32) + (1.0 - b1) * g
    new_v = b2 * nu.astype(jnp.float32) + (1.0 - b2) * g * g
    step = (new_m / bc1) / (jnp.sqrt(new_v / bc2) + eps)
    new_p = p32 - lr * (step + wd * p32)
    return (
        new_p.astype(param.dtype),
        new_m.astype(mu.dtype),
        new_v.astype(nu.dtype),
        g_agg,
    )


def fused_unpack_adamw_ref(
    words: jnp.ndarray, param: jnp.ndarray, mu: jnp.ndarray, nu: jnp.ndarray,
    *, bits: int, n_summed: int, **kw
):
    """unpack_words_ref composed with fused_adamw_ref."""
    int_sum = unpack_words_ref(
        words, param.shape, bits=bits, n_summed=n_summed
    )
    return fused_adamw_ref(int_sum, param, mu, nu, **kw)


def block_norms_ref(x: jnp.ndarray, block_rows: int) -> jnp.ndarray:
    """Squared L2 norm of each contiguous row-block of a 2-D array."""
    rows = x.shape[0]
    nblocks = (rows + block_rows - 1) // block_rows
    pad = nblocks * block_rows - rows
    xf = jnp.pad(x.astype(jnp.float32), ((0, pad), (0, 0)))
    return jnp.sum(
        jnp.square(xf).reshape(nblocks, block_rows, x.shape[1]), axis=(1, 2)
    )
