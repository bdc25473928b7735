"""PackedInt — k sub-words bit-packed into each int32 lane (SwitchML-style).

Layout (the canonical wire layout, shared bit-for-bit by the Pallas kernels
in :mod:`repro.kernels`): the flat integer image of size d is zero-padded to
k·m with m = ceil(d/k) words, split into k contiguous chunks, and chunk j is
stored in bit-field j of every word::

    word[w] = Σ_j (flat[j·m + w] + lim) << (j·bits)        (mod 2^32)

Guard-bit / bias invariant: each field carries v + lim >= 0 with
lim = clip_limit(n) = (2^(bits-1)-1)//n, so the n-worker field sum is
Σ v_i + n·lim ∈ [0, 2n·lim] ⊆ [0, 2^bits - 2] — it NEVER carries into the
neighbouring field. Word addition wraps mod 2^32 (psum of int32), which is
exact for the per-field arithmetic; unpack shifts+masks each field out and
subtracts the accumulated bias n·lim. That is the psum-safety contract of
:class:`repro.wire.base.WireFormat`, proven by tests/test_wire.py.

Wire cost: 4·ceil(d/k) bytes per worker — bits/8 bytes per coordinate, i.e.
4× fewer than the int32 transport for the int8 recipe and 8× fewer for int4
(a width the dense transport cannot ride at all: its narrowest lane is int8).
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar, Tuple

import jax
import jax.numpy as jnp

from repro.wire.base import WireFormat

_ALLOWED_BITS = (4, 8, 16)


@dataclasses.dataclass(frozen=True)
class PackedInt(WireFormat):
    name: ClassVar[str] = "packed"

    bits: int = 8

    def __post_init__(self):
        if self.bits not in _ALLOWED_BITS:
            raise ValueError(
                f"PackedInt packs sub-int32 fields; bits must be one of "
                f"{_ALLOWED_BITS}, got {self.bits} (use DenseInt for int32)"
            )

    @property
    def fields(self) -> int:
        """Sub-words per int32 transport word."""
        return 32 // self.bits

    def words_len(self, size: int) -> int:
        return -(-int(size) // self.fields)

    def pack(self, ints: jax.Array, *, n_workers: int) -> jax.Array:
        lim = self.clip_limit(n_workers)
        if self.use_kernels:
            from repro.kernels import ops as kops

            return kops.pack_words(
                ints, bits=self.bits, n_workers=n_workers
            )
        k, b = self.fields, self.bits
        flat = ints.reshape(-1).astype(jnp.int32)
        m = self.words_len(flat.size)
        flat = jnp.pad(flat, (0, k * m - flat.size))
        # chunks are static 1-D slices, never a (k, m) array: the TPU
        # compiler takes minutes over a (k, m) view of a large image
        word = jnp.zeros((m,), jnp.int32)
        for j in range(k):  # k is static; the adds fuse into one pass
            word = word + ((flat[j * m : (j + 1) * m] + lim) << (j * b))
        return word

    def unpack(
        self, words: jax.Array, shape: Tuple[int, ...], *, n_summed: int
    ) -> jax.Array:
        lim = self.clip_limit(n_summed)
        size = 1
        for s in shape:
            size *= int(s)
        if self.use_kernels:
            from repro.kernels import ops as kops

            return kops.unpack_words(
                words, shape, bits=self.bits, n_summed=n_summed
            )
        k, b = self.fields, self.bits
        mask = (1 << b) - 1
        # arithmetic >> then mask keeps exactly original bits [j·b, (j+1)·b):
        # sign-extension only touches positions the mask drops.
        fields = [
            ((words >> (j * b)) & mask) - n_summed * lim for j in range(k)
        ]
        flat = jnp.concatenate(fields)[:size]  # chunk-major, as in pack
        return flat.astype(jnp.int32).reshape(shape)

    def wire_bytes(self, size: int) -> int:
        return 4 * self.words_len(size)

    def fused_update(self, words, param, opt, scalars, *, kernel: str,
                     n_summed: int, shift=None):
        from repro.kernels import ops as kops

        return kops.fused_unpack_apply(
            words, param, tuple(opt), scalars, shift,
            kernel=kernel, bits=self.bits, n_summed=n_summed,
        )
