"""WireFormat — the pluggable codec between "rounded integers" and the wire.

The paper's headline property is a wire that carries *no floats*. Everything
that happens between a worker's float gradient and the aggregated integer
image is the wire codec's business, split into four orthogonal stages::

    encode : f32 tensor, α, key  ->  clipped integer image (canonical int32)
    pack   : integer image       ->  transport PAYLOAD (≥1 integer planes)
    unpack : transported payload ->  summed integer image (int32)
    decode : summed image, α     ->  gradient estimate (1/(nα)) Σ Int(α g_i)

A payload is a pytree of integer PLANES. The two transport shapes:

* ``transport = "psum"`` (DenseInt, PackedInt): pack returns a single
  summable plane — a bare array of transport words — and the wire is an
  integer all-reduce of that plane. Psum-safety contract::

      unpack(Σ_i pack(ints_i), n) == Σ_i ints_i     elementwise, exactly,

  for any n tensors whose entries respect the §5.1 clip
  |v| <= clip_limit(n). The Σ on the left is the wire all-reduce in the
  transport-word dtype (wrap-around integer addition); the Σ on the right is
  the mathematical sum.

* ``transport = "gather"`` (TopKInt): pack returns a dict of named planes
  (``plane_names``) whose coordinates are only meaningful together — e.g. a
  value plane plus the index plane that positions it — so no sum may cross
  the wire. The transport is an integer all-gather of the payload and unpack
  receives every plane with a leading worker axis of length ``n_summed``.
  Gather-safety contract::

      unpack(stack_i(pack(ints_i)), n) == Σ_i local_image(ints_i)

  where :meth:`local_image` is the lossy image one worker's payload decodes
  to (identity for psum codecs; the top-k-masked image for sparse ones).

Either way the compressor reasons about exact integer sums while the
transport representation stays swappable (dense lanes, bit-packed words,
sparse value+index planes, future entropy-coded wires).

Call sites select a codec through the compressor's ``wire`` field (or the
``wire=`` argument of ``launch.step.build_train_step``); new transports
extend :mod:`repro.wire`, not the call sites.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar, Tuple

import jax
import jax.numpy as jnp

# NOTE: no module-level repro.core imports here (or anywhere in repro.wire):
# core/compressor.py imports this package, so the wire package must be
# importable standalone; the Int-operator primitives are pulled lazily.

__all__ = ["WireFormat", "WireRangeError", "clip_limit", "payload_nbytes"]

_INT_RANGE = {4: 7, 8: 127, 16: 32767, 32: 2147483647}


class WireRangeError(ValueError):
    """The wire configuration cannot represent the n-worker sum.

    Raised when the §5.1 clip limit ``(2^(b-1)-1) // n_workers`` degenerates
    to 0 — every local integer would be clipped to 0 and the whole gradient
    silently zeroed (e.g. 256 workers on an int8 wire). The fix is a wider
    wire (`bits`) or fewer workers per integer all-reduce group.
    """


def clip_limit(*, n_workers: int, bits: int) -> int:
    """The §5.1 clip limit: largest |v| such that the n-worker sum fits
    `bits`. Raises :class:`WireRangeError` on the degenerate range."""
    if bits not in _INT_RANGE:
        raise ValueError(f"unsupported wire width {bits}")
    lim = _INT_RANGE[bits] // max(n_workers, 1)
    if lim == 0:
        raise WireRangeError(
            f"int{bits} wire cannot carry a sum over {n_workers} workers: "
            f"clip limit (2^{bits - 1}-1)//{n_workers} == 0 would zero every "
            f"gradient. Use a wider wire (bits>={bits * 2}) or fewer workers "
            f"per integer all-reduce group."
        )
    return lim


def payload_nbytes(payload) -> int:
    """Exact bytes of one payload (tree-sum over its integer planes).

    Works on concrete arrays and abstract ShapeDtypeStructs alike — this is
    the single definition :class:`repro.wire.logged.Logged` meters with, so
    psum payloads (one plane) and gather payloads (several) are counted the
    same way.
    """
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(payload))


@dataclasses.dataclass(frozen=True)
class WireFormat:
    """Base codec: shared encode/decode; transport stages are per-format.

    ``bits`` is the VALUE width of one transported coordinate. How those
    values ride the physical lanes is what subclasses define via
    pack/unpack: the *payload* a subclass packs is a pytree of integer
    planes — a single summable word plane for psum-transport codecs (one
    narrow lane per coordinate, or several coordinates packed into an int32
    word), or several named planes (``plane_names``, e.g. values + indices)
    for gather-transport codecs where no cross-worker sum is legal on the
    wire. ``transport`` declares which collective shape the payload rides;
    ``fused_capable`` declares whether the codec has a fused decode+update
    kernel (the codec half of the capability dispatch in
    ``launch.step._fused_plan``).
    """

    name: ClassVar[str] = "base"
    transport: ClassVar[str] = "psum"  # "psum" | "gather"
    plane_names: ClassVar[Tuple[str, ...]] = ("words",)
    fused_capable: ClassVar[bool] = True

    bits: int = 32
    use_kernels: bool = False  # route hot stages through the Pallas kernels

    # ---- shared value stages -------------------------------------------
    def clip_limit(self, n_workers: int) -> int:
        """§5.1 limit; raises WireRangeError when it degenerates to 0."""
        return clip_limit(n_workers=n_workers, bits=self.bits)

    def encode(
        self,
        x: jax.Array,
        alpha: jax.Array,
        key: jax.Array | None,
        *,
        n_workers: int,
        stochastic: bool = True,
    ) -> jax.Array:
        """x -> Int(α ∘ x) clipped for the n-worker sum, canonical int32."""
        lim = self.clip_limit(n_workers)
        if self.use_kernels:
            from repro.kernels import ops as kops

            return kops.int_compress(
                x, alpha, key, n_workers=n_workers, bits=self.bits,
                stochastic=stochastic,
            )
        from repro.core import rounding  # lazy: core imports this package

        r = rounding.int_round(
            x.astype(jnp.float32) * alpha, key, stochastic=stochastic
        )
        return jnp.clip(r, -lim, lim).astype(jnp.int32)

    def decode(
        self, ints: jax.Array, alpha: jax.Array, *, n_workers: int
    ) -> jax.Array:
        """Summed integer image -> gradient estimate (1/(nα)) Σ Int(α g_i).
        Multiplies by the reciprocal, as the fused kernels do with their
        ``inv_nalpha`` scalar, so both update routes decode bit-identically."""
        return ints.astype(jnp.float32) * (1.0 / (n_workers * alpha))

    # ---- transport stages (per-format) ---------------------------------
    def pack(self, ints: jax.Array, *, n_workers: int):
        """Integer image -> transport payload.

        Psum codecs return a single summable plane (a bare array of words);
        gather codecs return a dict of ``plane_names`` planes. All planes of
        one codec share a single integer dtype so the bucketed route can
        concatenate them.
        """
        raise NotImplementedError

    def unpack(self, payload, shape: Tuple[int, ...], *, n_summed: int) -> jax.Array:
        """Transported payload -> summed integer image (int32).

        For psum codecs ``payload`` is the all-reduced word plane and
        ``n_summed`` the number of contributions folded into it (needed to
        strip n× biases). For gather codecs every plane arrives with a
        leading worker axis of length ``n_summed`` and unpack performs the
        sum itself (scatter-add of each worker's contribution).
        """
        raise NotImplementedError

    def local_image(self, ints: jax.Array, *, n_workers: int) -> jax.Array:
        """The integer image the decoder attributes to THIS worker.

        Identity for lossless-transport (psum) codecs. Sparse codecs
        override it with the same selection pack performs (top-k mask), so
        error-feedback compressors can compute the transmitted-vs-encoded
        residual without unpacking their own payload.
        """
        return ints

    def wire_bytes(self, size: int) -> int:
        """Exact bytes one worker's `size`-coordinate payload puts on the
        collective, summed over all planes (the quantity bench_comm_volume
        meters)."""
        raise NotImplementedError

    def fused_update(
        self,
        words: jax.Array,
        param: jax.Array,
        opt: Tuple[jax.Array, ...],
        scalars: jax.Array,
        *,
        kernel: str,
        n_summed: int,
        shift: jax.Array | None = None,
    ):
        """Fused decode + optimizer step straight off the transport words
        (the Pallas route) — the codec half of the capability-dispatch
        contract. ``kernel`` names the optimizer arithmetic
        (``Optimizer.fused_kernel``: "sgd" | "adamw"), ``opt`` carries that
        kernel's per-leaf f32 state tensors in
        ``optim.base.FUSED_STATE_TENSORS`` order, and ``scalars`` the
        canonical f32 vector documented in :mod:`repro.kernels.fused_update`
        (``[inv_nalpha, clip, *FUSED_SCALAR_TAIL[kernel]]``). ``shift`` is
        the optional replicated global shift h (IntDIANA): the kernel
        decodes g = h + Σints·inv_nalpha and emits the new shift (= g)
        alongside.

        Returns ``(new_param, new_opt, new_shift | None)`` without
        materializing the unpacked integer image in HBM."""
        raise NotImplementedError
