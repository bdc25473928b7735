"""Gradient compressors: IntSGD (ours, all variants) + the paper's baselines.

Every compressor implements::

    init(params)                         -> state (replicated pytree)
    aggregate(state, grads, key, eta, ctx) -> (ghat, new_state, metrics)

where ``grads`` is the *local* gradient pytree of one worker and ``ctx`` is a
:class:`repro.core.comm.CommCtx`. ``ghat`` is the aggregated (averaged)
gradient estimate, identical on every worker. ``metrics`` reports wire
statistics (max |integer| on the wire, estimated bits/coordinate, payload
bytes) used by tests and the paper-table benchmarks.

Aggregation semantics per family:

  * all-reduce compatible (IntSGD, Heuristic IntSGD, PowerSGD, SignSGD, none):
    the payload is *summed* across workers in one psum — unless the
    configured wire codec declares a gather transport (TopKInt's value+index
    planes), in which case ``CommCtx.psum_wire`` all-gathers the integer
    payload and the codec's unpack performs the sum by scatter-add;
  * all-gather only (QSGD, NatSGD, TopK): payloads are gathered and each
    worker decodes all n of them — the expensive path the paper's Tables 2/3
    quantify; our roofline benchmark reproduces that comparison from HLO
    collective bytes.

IntSGD state-update split: α depends on r_k, which depends on the *model
update* of the previous step. The optimizer wrapper calls
``observe_update(state, delta_x)`` after applying the step; ``aggregate`` only
reads the current state. The first optimization step must use exact
aggregation (paper §4.1 "the first communication is exact") — drivers call
``aggregate_exact`` at k=0 and the compressed step thereafter.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, ClassVar, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel import collectives as coll

from repro.core.comm import CommCtx, fold_worker_key
from repro.core.stats import DxStats, TreeDims, local_tree_dims
from repro.launch import stages
from repro.wire import DenseInt, WireFormat, make_wire_format
from repro.core.scaling import (
    AlphaBlockwise,
    AlphaDiana,
    AlphaHeuristic,
    AlphaLastStep,
    AlphaMovingAvg,
    AlphaRule,
)
from repro.utils.tree import tree_abs_max, tree_size, tree_sq_norm


def _leaf_dims(params):
    return jax.tree.map(lambda x: float(x.size), params)


@stages.scoped("wire")
def aggregate_exact(grads, ctx: CommCtx):
    """Full-precision mean over workers (step-0 / no-compression path)."""
    return ctx.pmean(grads)


def _leaf_keys(key, tree):
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(treedef, list(jax.random.split(key, len(leaves))))


def _payload_bytes(wf: WireFormat, tree) -> float:
    """Static per-worker collective payload under codec `wf` (exact bytes)."""
    return float(sum(wf.wire_bytes(l.size) for l in jax.tree.leaves(tree)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WireAggregate:
    """What came back from the integer all-reduce.

    ``words`` is the summed transport payload exactly as it crossed the
    wire (packed int32 words / narrow lanes) — the fused Pallas update
    consumes it directly. ``ints`` is the unpacked summed integer image
    Σ_i Int(α g_i) (canonical int32) for decode, clipping and metrics; XLA
    fuses its unpack into whatever reduction consumes it, so keeping both
    views costs no extra HBM traffic on the fused route.
    """

    words: Any
    ints: Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Metrics:
    max_int: jax.Array  # max |aggregated integer| on the wire (0 for float paths)
    bits_per_coord: jax.Array  # estimated wire bits per coordinate
    payload_bytes: float = dataclasses.field(
        metadata=dict(static=True)
    )  # static: bytes sent per worker per step
    # max over workers of the LOCAL payload |Int(α g_i)|∞ — the per-worker
    # wire-width requirement; this is the quantity that blows up for IntGD on
    # heterogeneous data and that IntDIANA bounds (Appendix A.2 / Fig. 6)
    max_local_int: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.zeros(())
    )


class Compressor:
    supports_allreduce: ClassVar[bool] = True
    name: ClassVar[str] = "base"
    # --- fused/wire-level capability (the compressor half of the fused-route
    # contract; the optimizer half is Optimizer.fused_kernel) ----------------
    # fused_capable: the compressor exposes its aggregation at WIRE level —
    # ``encode_ints`` (per-image encode, microbatch pipelining),
    # ``aggregate_wire`` (encode+reduce without decoding, the fused Pallas
    # entry), ``finish_pipelined`` (decode + state advance of accumulated
    # images) and the shift hooks below. launch/step.py routes the fused
    # update AND the microbatch wire pipelining on this flag alone.
    fused_capable: ClassVar[bool] = False
    # fused_local_state: state updates consume the LOCAL integer image
    # (IntDIANA's h_local); the pipelined train body accumulates it only
    # when this is set.
    fused_local_state: ClassVar[bool] = False

    def init(self, params) -> Any:
        return ()

    def observe_update(self, state, dx_stats: DxStats):
        """Called by the optimizer after x^{k+1} = x^k - η ĝ with the GLOBAL
        ||Δx||² statistics (see repro.core.stats)."""
        return state

    def aggregate(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        raise NotImplementedError

    # --- fused-route shift hooks (no-ops unless the compressor carries a
    # replicated shift the decode must add, like IntDIANA's h_global) -------
    def fused_shift(self, state):
        """Replicated global-shift tree the fused kernel adds to the decoded
        aggregate (g = shift + Σints/(nα)), or None."""
        return None

    def fused_store_shift(self, state, new_shift):
        """Fold the kernel's emitted shift output back into the state."""
        return state


# --------------------------------------------------------------------------
# Full precision (the SGD baseline; also what step 0 of IntSGD uses)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class NoCompression(Compressor):
    name: ClassVar[str] = "none"
    # all-gather flavour exists purely to reproduce the paper's
    # SGD (All-gather) row; semantics are identical.
    use_allgather: bool = False

    def aggregate(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        d = tree_size(grads)
        if self.use_allgather:
            gathered = ctx.all_gather(grads)
            ghat = jax.tree.map(lambda g: jnp.mean(g, axis=0), gathered)
            payload = 4.0 * d * ctx.n
        else:
            ghat = ctx.pmean(grads)
            payload = 4.0 * d
        m = Metrics(jnp.zeros(()), jnp.full((), 32.0), payload)
        return ghat, state, m


# --------------------------------------------------------------------------
# IntSGD (ours) — global / blockwise α, stochastic / deterministic rounding
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class IntSGD(Compressor):
    """Algorithm 1 (global α) / Algorithm 2 (blockwise α).

    The transport representation is delegated to a :class:`WireFormat`
    (``wire``); ``bits``/``use_kernels`` are the legacy shorthand for the
    dense codec and are folded into the default ``DenseInt`` when no codec
    is given explicitly.

    Sparse (gather-transport) codecs drop coordinates, so IntSGD carries an
    EF21-style error-feedback residual for them: the state becomes
    ``{"alpha": AlphaState, "ef": residual tree}``, each step encodes
    ``work = grad + residual`` and feeds back
    ``residual' = work − local_image/α`` — exactly the per-worker decode
    error, quantization and sparsification both. Lossless (psum) codecs
    keep the bare AlphaState and an identical trajectory to before.
    """

    name: ClassVar[str] = "intsgd"
    alpha_rule: AlphaRule = AlphaMovingAvg()
    bits: int = 32
    stochastic: bool = True
    use_kernels: bool = False  # route encode/pack through Pallas kernels
    wire: WireFormat | None = None

    @property
    def fused_capable(self) -> bool:  # type: ignore[override]
        """Delegates to the codec: the fused decode+update route (and the
        microbatch wire pipelining built on it) needs the wire's fused
        kernel, which sparse codecs don't have."""
        return bool(getattr(self.wire_format, "fused_capable", True))

    @property
    def blockwise(self) -> bool:
        return isinstance(self.alpha_rule, AlphaBlockwise)

    @property
    def wire_format(self) -> WireFormat:
        if self.wire is not None:
            return self.wire
        return DenseInt(bits=self.bits, use_kernels=self.use_kernels)

    @property
    def _carries_residual(self) -> bool:
        return getattr(self.wire_format, "transport", "psum") == "gather"

    @staticmethod
    def _split_state(state):
        """State -> (alpha_state, residual | None)."""
        if isinstance(state, dict) and set(state) == {"alpha", "ef"}:
            return state["alpha"], state["ef"]
        return state, None

    def init(self, params):
        alpha = self.alpha_rule.init(params)
        if self._carries_residual:
            ef = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            return {"alpha": alpha, "ef": ef}
        return alpha

    def observe_update(self, state, dx_stats: DxStats):
        alpha, ef = self._split_state(state)
        alpha = self.alpha_rule.update(alpha, dx_stats)
        if ef is not None:
            return {"alpha": alpha, "ef": ef}
        return alpha

    @stages.scoped("alpha")
    def _alphas(self, state, grads, eta, n, dims: TreeDims | None):
        if dims is None:
            dims = local_tree_dims(grads)
        if self.blockwise:
            a = self.alpha_rule.alpha_tree(
                state, eta, n, dims.leaf_dims, float(dims.d)
            )
        else:
            a_scalar = self.alpha_rule.alpha(state, eta, n, dims.d)
            a = jax.tree.map(lambda _: a_scalar, grads)
        return a

    def encode_ints(
        self, state, grads, *, key, eta, ctx: CommCtx, dims=None,
        n_accum: int = 1,
    ):
        """One worker's §5.1-clipped integer image Int(α∘x) and the α tree —
        the encode stage alone, no wire traffic. The overlapped train body
        (launch/step.py microbatch pipelining) calls this per microbatch so
        each image's bucketed reduce can launch while the next microbatch's
        backward is still running; ``aggregate_wire`` is the single-shot
        encode+reduce composition.

        ``n_accum`` is how many summed images the caller will ACCUMULATE on
        top of the n-worker wire sum (M for M-microbatch pipelining): the
        clip tightens to ``clip_limit(n·n_accum)`` so the full accumulated
        sum still fits the value width — without it an int32 wire with
        M > 1 could wrap the int32 accumulator on clip-saturating
        gradients. The transport itself still packs/unpacks with n (only n
        payloads ride each psum), which the tighter clip keeps safe.

        When the codec is sparse the encoded tensor is ``work = grad +
        residual`` (error feedback); the residual advance itself lives in
        ``aggregate_wire`` — the pipelined path never reaches here with a
        sparse codec because its ``fused_capable`` is False."""
        n = ctx.n
        wf = self.wire_format
        alpha_state, ef = self._split_state(state)
        work = grads
        if ef is not None:
            with stages.stage("encode"):
                work = jax.tree.map(
                    lambda g, r: g.astype(jnp.float32) + r, grads, ef
                )
        alphas = self._alphas(alpha_state, work, eta, n, dims)
        with stages.stage("encode"):
            akeys = _leaf_keys(fold_worker_key(key, ctx), work)
            ints = jax.tree.map(
                lambda g, a, k: wf.encode(
                    g, a, k, n_workers=n * n_accum, stochastic=self.stochastic
                ),
                work,
                alphas,
                akeys,
            )
        return ints, alphas

    def aggregate_wire(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        """Wire-level aggregation: returns the summed wire payload (packed
        words + integer image, see :class:`WireAggregate`) and the α tree
        *without* decoding. This is the entry point the fused decode+update
        kernel routing (launch/step.py) builds on — the decode 1/(nα) is
        folded into the Pallas optimizer kernel instead of materializing ĝ.
        ``aggregate`` is the decode-here wrapper."""
        n = ctx.n
        wf = self.wire_format
        ints, alphas = self.encode_ints(
            state, grads, key=key, eta=eta, ctx=ctx, dims=dims
        )
        with stages.stage("counters"):
            max_local = coll.pmax(tree_abs_max(ints), ctx.axes)
        # THE wire: codec-packed integer aggregation. On TPU this is the ICI
        # collective carrying only integer transport planes — the paper's
        # INA/all-reduce analog, at bits/8 bytes per coordinate for the
        # packed codec, or the gathered vals+idx planes for sparse ones.
        words_sum, int_sum = ctx.psum_wire(ints, wf)
        alpha_state, ef = self._split_state(state)
        if ef is not None:
            # EF21 advance: what the wire dropped (or rounded away) of this
            # worker's work tensor is carried into the next step. local_image
            # re-derives the transmitted selection from the same ints —
            # XLA CSEs it against pack's top_k, so no second selection runs.
            with stages.stage("encode"):
                work = jax.tree.map(
                    lambda g, r: g.astype(jnp.float32) + r, grads, ef
                )
                local = jax.tree.map(
                    lambda v: wf.local_image(v, n_workers=n), ints
                )
                ef = jax.tree.map(
                    lambda w, l, a: w - l.astype(jnp.float32) / a,
                    work, local, alphas,
                )
            state = {"alpha": alpha_state, "ef": ef}
        with stages.stage("counters"):
            max_int = tree_abs_max(int_sum)
            bits = 1.0 + jnp.ceil(jnp.log2(jnp.maximum(max_int, 1.0) + 1.0))
        payload = _payload_bytes(wf, grads)
        return (
            WireAggregate(words=words_sum, ints=int_sum),
            alphas,
            state,
            Metrics(max_int, bits, payload, max_local),
        )

    def aggregate(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        wa, alphas, state, metrics = self.aggregate_wire(
            state, grads, key=key, eta=eta, ctx=ctx, dims=dims
        )
        wf = self.wire_format
        with stages.stage("decode"):
            ghat = jax.tree.map(
                lambda s, a: wf.decode(s, a, n_workers=ctx.n), wa.ints, alphas
            )
        return ghat, state, metrics

    def finish_pipelined(
        self, state, int_sum_acc, local_int_acc, alphas, *, ctx: CommCtx,
        n_accum: int,
    ):
        """Decode the n_accum accumulated summed images of the microbatch-
        pipelined train body: ĝ = (1/(n·M·α)) Σ_m Σ_i Int(α g_i^m). The
        per-image clip (``encode_ints(n_accum=M)``) guarantees the int32
        accumulator never wrapped. IntSGD carries no wire-level state, so
        ``local_int_acc`` (None here — fused_local_state is False) is
        unused and the state passes through."""
        del local_int_acc
        wf = self.wire_format
        with stages.stage("decode"):
            ghat = jax.tree.map(
                lambda s, a: wf.decode(s, a, n_workers=ctx.n * n_accum),
                int_sum_acc,
                alphas,
            )
        return ghat, state


# --------------------------------------------------------------------------
# Heuristic IntSGD (Sapio et al. 2021) — profiling max-reduce, fixed α
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HeuristicIntSGD(Compressor):
    name: ClassVar[str] = "heuristic_intsgd"
    bits: int = 8
    stochastic: bool = False
    wire: WireFormat | None = None

    @property
    def wire_format(self) -> WireFormat:
        return self.wire if self.wire is not None else DenseInt(bits=self.bits)

    def init(self, params):
        return ()

    def aggregate(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        n = ctx.n
        wf = self.wire_format
        rule = AlphaHeuristic(bits=self.bits)
        local_absmax = jnp.max(
            jnp.stack([jnp.max(jnp.abs(l)) for l in jax.tree.leaves(grads)])
        )
        # the profiling step: an extra float max-reduce before every round —
        # this is exactly the overhead the paper's adaptive rule removes.
        global_absmax = ctx.pmax_global(local_absmax)
        alpha = rule.alpha_from_absmax(global_absmax, n)
        akeys = _leaf_keys(fold_worker_key(key, ctx), grads)
        # The heuristic α bounds |αg| <= (2^(b-1)-1)/n, but rounding can
        # nudge a coordinate one past that bound — and neither a packed
        # field nor a narrow dense lane has any slack for the n-worker sum
        # (4 workers at 32 when α said 31.75 wraps an int8 psum). So the
        # hard §5.1 sum-clip applies on every codec; it only bites in the
        # rounding-nudge case the α bound already aimed to exclude.
        ints = jax.tree.map(
            lambda g, k: wf.encode(
                g, alpha, k, n_workers=n, stochastic=self.stochastic
            ),
            grads,
            akeys,
        )
        _, int_sum = ctx.psum_wire(ints, wf)
        ghat = jax.tree.map(lambda s: wf.decode(s, alpha, n_workers=n), int_sum)
        max_int = tree_abs_max(int_sum)
        bits = 1.0 + jnp.ceil(jnp.log2(jnp.maximum(max_int, 1.0) + 1.0))
        return ghat, state, Metrics(max_int, bits, _payload_bytes(wf, grads))


# --------------------------------------------------------------------------
# QSGD (Alistarh et al. 2017) — all-gather only
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class QSGD(Compressor):
    """QSGD with an optional wire codec for the gathered integer payload.

    With ``wire=None`` this is the paper-faithful transport: one int8 level
    lane + one int8 sign lane per coordinate. With a codec, the signed level
    v = sign·q ∈ [-levels, levels] rides the codec's transport words instead
    (all-gather, so pack/unpack use n_workers=1 — no sum crosses the wire);
    PackedInt(8) halves the gathered bytes vs the two-lane layout.
    """

    name: ClassVar[str] = "qsgd"
    supports_allreduce: ClassVar[bool] = False
    levels: int = 64  # 6-bit, matching the paper's setup
    wire: WireFormat | None = None

    def init(self, params):
        return ()

    def _quantize_leaf(self, g, key):
        norm = jnp.linalg.norm(g.astype(jnp.float32).reshape(-1)) + 1e-30
        scaled = jnp.abs(g.astype(jnp.float32)) / norm * self.levels
        lo = jnp.floor(scaled)
        p = scaled - lo
        u = jax.random.uniform(key, g.shape, dtype=jnp.float32)
        q = lo + (u < p).astype(jnp.float32)
        return q, norm.astype(jnp.float32)

    def _encode_leaf(self, g, key):
        q, norm = self._quantize_leaf(g, key)
        return q.astype(jnp.int8), jnp.sign(g).astype(jnp.int8), norm

    @property
    def _bits_per_coord(self) -> float:
        """Wire bits per coordinate: level field + sign."""
        return 1.0 + math.ceil(math.log2(self.levels + 1))

    def aggregate(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        akeys = _leaf_keys(fold_worker_key(key, ctx), grads)
        is_shaped = lambda x: hasattr(x, "shape")
        if self.wire is not None:
            wf = self.wire
            if getattr(wf, "transport", "psum") == "gather":
                raise ValueError(
                    "QSGD's gathered level payload needs a psum-shaped "
                    "(dense/packed) codec; a gather-transport codec like "
                    f"{wf.name!r} cannot carry it"
                )
            if wf.clip_limit(1) < self.levels:
                raise ValueError(
                    f"wire bits={wf.bits} too narrow for {self.levels} levels"
                )

            def enc(g, k):
                q, norm = self._quantize_leaf(g, k)
                v = (q * jnp.sign(g.astype(jnp.float32))).astype(jnp.int32)
                return wf.pack(v, n_workers=1), norm

            enc_tree = jax.tree.map(enc, grads, akeys, is_leaf=is_shaped)
            gathered = ctx.all_gather(enc_tree)

            def dec(leaf, g_like):
                words, norm = leaf
                vals = jax.vmap(
                    lambda w: wf.unpack(w, g_like.shape, n_summed=1)
                )(words).astype(jnp.float32)
                vals = vals * (
                    norm.reshape((-1,) + (1,) * g_like.ndim) / self.levels
                )
                return jnp.mean(vals, axis=0)

            ghat = jax.tree.map(
                dec, gathered, grads, is_leaf=lambda x: isinstance(x, tuple)
            )
            payload = _payload_bytes(wf, grads) + 4.0 * len(jax.tree.leaves(grads))
            return ghat, state, Metrics(
                jnp.zeros(()), jnp.full((), self._bits_per_coord), payload
            )

        enc = jax.tree.map(self._encode_leaf, grads, akeys, is_leaf=is_shaped)
        # all-gather of (levels, signs, norm): the expensive primitive
        gathered = ctx.all_gather(enc)

        def dec(leaf):
            q, s, norm = leaf
            vals = q.astype(jnp.float32) * s.astype(jnp.float32)
            vals = vals * (norm.reshape((-1,) + (1,) * (q.ndim - 1)) / self.levels)
            return jnp.mean(vals, axis=0)

        ghat = jax.tree.map(dec, gathered, is_leaf=lambda x: isinstance(x, tuple))
        d = tree_size(grads)
        # entropy-coded estimate: level bits + sign bit + norms, per worker
        payload = d * (self._bits_per_coord + 2.0) / 8.0
        return ghat, state, Metrics(
            jnp.zeros(()), jnp.full((), self._bits_per_coord), payload
        )


# --------------------------------------------------------------------------
# NatSGD — natural compression (Horváth et al. 2019), all-gather only
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class NatSGD(Compressor):
    name: ClassVar[str] = "natsgd"
    supports_allreduce: ClassVar[bool] = False

    def init(self, params):
        return ()

    def _encode_leaf(self, g, key):
        g = g.astype(jnp.float32)
        mag = jnp.abs(g)
        safe = jnp.maximum(mag, 1e-38)
        e_lo = jnp.floor(jnp.log2(safe))
        p_up = mag / jnp.exp2(e_lo) - 1.0  # prob of rounding exponent up
        u = jax.random.uniform(key, g.shape, dtype=jnp.float32)
        e = e_lo + (u < p_up).astype(jnp.float32)
        e = jnp.where(mag == 0, -127.0, e)
        return jnp.clip(e, -126.0, 126.0).astype(jnp.int8), jnp.sign(g).astype(jnp.int8)

    def aggregate(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        wkey = fold_worker_key(key, ctx)
        leaves, treedef = jax.tree.flatten(grads)
        akeys = jax.tree.unflatten(treedef, list(jax.random.split(wkey, len(leaves))))
        enc = jax.tree.map(self._encode_leaf, grads, akeys, is_leaf=lambda x: hasattr(x, "shape"))
        gathered = ctx.all_gather(enc)

        def dec(leaf):
            e, s = leaf
            vals = jnp.where(
                e.astype(jnp.float32) <= -127.0,
                0.0,
                jnp.exp2(e.astype(jnp.float32)) * s.astype(jnp.float32),
            )
            return jnp.mean(vals, axis=0)

        ghat = jax.tree.map(dec, gathered, is_leaf=lambda x: isinstance(x, tuple))
        d = tree_size(grads)
        return ghat, state, Metrics(jnp.zeros(()), jnp.full((), 9.0), d * 1.125)


# --------------------------------------------------------------------------
# PowerSGD (Vogels et al. 2019) + error feedback — all-reduce compatible
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PowerSGD(Compressor):
    name: ClassVar[str] = "powersgd"
    rank: int = 2
    ef: bool = True
    min_compress_size: int = 4096  # small tensors stay uncompressed (float psum)

    def _is_matrix(self, x):
        return x.ndim >= 2 and x.size >= self.min_compress_size

    def init(self, params):
        def q_init(x):
            if not self._is_matrix(x):
                return None
            m = x.reshape(x.shape[0], -1)
            k = jax.random.PRNGKey(abs(hash(str(m.shape))) % (2**31))
            return jax.random.normal(k, (m.shape[1], self.rank), jnp.float32)

        qs = jax.tree.map(q_init, params)
        errs = jax.tree.map(jnp.zeros_like, params) if self.ef else None
        return {"q": qs, "err": errs}

    @staticmethod
    def _orthonormalize(p):
        # modified Gram-Schmidt, numerically adequate for small ranks
        q, _ = jnp.linalg.qr(p)
        return q

    def aggregate(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        n = ctx.n
        errs = state["err"]
        work = (
            jax.tree.map(jnp.add, grads, errs) if self.ef else grads
        )

        def comp(m, q):
            if q is None:
                return None
            m2 = m.reshape(m.shape[0], -1).astype(jnp.float32)
            p = m2 @ q  # (rows, rank)
            p = coll.psum(p, ctx.axes) / n  # all-reduce #1 (small!)
            p_hat = self._orthonormalize(p)
            qn = m2.T @ p_hat  # (cols, rank)
            qn = coll.psum(qn, ctx.axes) / n  # all-reduce #2
            approx = (p_hat @ qn.T).reshape(m.shape)
            return approx, qn

        q_leaf = lambda x: x is None
        outs = jax.tree.map(
            lambda m, q: comp(m, q), work, state["q"], is_leaf=q_leaf
        )
        # `outs` leaves are (approx, qn) tuples or None — stop traversal there
        o_leaf = lambda x: x is None or (
            isinstance(x, tuple) and len(x) == 2 and hasattr(x[0], "shape")
        )

        def pick_ghat(m, o):
            if o is None:
                return coll.psum(m, ctx.axes) / n  # uncompressed small tensors
            return o[0]

        def pick_q(o, q_old):
            return q_old if o is None else o[1]

        ghat = jax.tree.map(pick_ghat, work, outs, is_leaf=o_leaf)
        new_q = jax.tree.map(pick_q, outs, state["q"], is_leaf=o_leaf)
        if self.ef:
            new_err = jax.tree.map(
                lambda w, g, o: jnp.zeros_like(w) if o is None else (w - g),
                work,
                ghat,
                outs,
                is_leaf=o_leaf,
            )
        else:
            new_err = None
        d = tree_size(grads)
        return (
            ghat,
            {"q": new_q, "err": new_err},
            Metrics(jnp.zeros(()), jnp.full((), 32.0), 4.0 * d * 0.05),
        )


# --------------------------------------------------------------------------
# SignSGD + EF (Karimireddy et al. 2019) — scaled sign, all-reduce of int8
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SignSGD(Compressor):
    name: ClassVar[str] = "signsgd"
    ef: bool = True

    def init(self, params):
        return jax.tree.map(jnp.zeros_like, params) if self.ef else ()

    def aggregate(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        n = ctx.n
        work = jax.tree.map(jnp.add, grads, state) if self.ef else grads

        def comp(w):
            w32 = w.astype(jnp.float32)
            scale = jnp.mean(jnp.abs(w32))  # ||w||_1 / d
            signs = jnp.sign(w32).astype(jnp.int8)
            local = scale * signs.astype(jnp.float32)  # C(p_i), what worker i sends
            # wire: int8 sign psum + one scalar psum (all-reduce compatible)
            ghat_leaf = coll.psum(local, ctx.axes) / n
            return ghat_leaf, local

        outs = jax.tree.map(comp, work)
        ghat = jax.tree.map(lambda o: o[0], outs, is_leaf=lambda x: isinstance(x, tuple))
        # EF uses each worker's OWN compressed output: e_i' = p_i - C(p_i)
        new_state = (
            jax.tree.map(
                lambda w, o: w - o[1],
                work,
                outs,
                is_leaf=lambda x: isinstance(x, tuple),
            )
            if self.ef
            else ()
        )
        d = tree_size(grads)
        return ghat, new_state, Metrics(jnp.zeros(()), jnp.full((), 1.0), d / 8.0)


# --------------------------------------------------------------------------
# Top-K + EF — all-gather of (values, indices)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TopK(Compressor):
    name: ClassVar[str] = "topk"
    supports_allreduce: ClassVar[bool] = False
    k_frac: float = 0.01
    ef: bool = True

    def init(self, params):
        return jax.tree.map(jnp.zeros_like, params) if self.ef else ()

    def aggregate(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        n = ctx.n
        work = jax.tree.map(jnp.add, grads, state) if self.ef else grads

        def comp(w):
            flat = w.astype(jnp.float32).reshape(-1)
            k = max(1, int(self.k_frac * flat.size))
            _, idx = lax.top_k(jnp.abs(flat), k)
            vals = flat[idx]
            local = jnp.zeros_like(flat).at[idx].set(vals)  # C(p_i)
            g_vals = ctx.all_gather(vals)  # (n, k)
            g_idx = ctx.all_gather(idx)  # (n, k)
            out = jnp.zeros_like(flat)
            out = out.at[g_idx.reshape(-1)].add(g_vals.reshape(-1))
            return (out / n).reshape(w.shape), local.reshape(w.shape)

        outs = jax.tree.map(comp, work)
        ghat = jax.tree.map(lambda o: o[0], outs, is_leaf=lambda x: isinstance(x, tuple))
        new_state = (
            jax.tree.map(
                lambda w, o: w - o[1],
                work,
                outs,
                is_leaf=lambda x: isinstance(x, tuple),
            )
            if self.ef
            else ()
        )
        d = tree_size(grads)
        return ghat, new_state, Metrics(
            jnp.zeros(()), jnp.full((), 32.0 * self.k_frac * 2), 8.0 * d * self.k_frac
        )


# --------------------------------------------------------------------------
# IntDIANA (Algorithm 3) — compress gradient differences with local shifts
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class IntDIANA(Compressor):
    """Algorithm 3. Local shift h_i lives on each worker (it is NOT replicated
    across the data axes — in the distributed runtime it is per-device state);
    the global shift h is replicated. Fixes the heterogeneous-data max-int
    blowup of plain IntSGD (Appendix A.2, Fig. 6).

    Wire-level split (fused_capable): ``aggregate_wire`` encodes the
    difference image Int(α(g_i - h_i)), advances h_local off that LOCAL
    image and reduces — WITHOUT decoding or touching h_global. The decode
    ĝ = h_global + (1/(nα))Σints then happens either here (``aggregate``) or
    inside the fused Pallas kernel, which takes h_global as its ``shift``
    input and emits the new h_global (= ĝ) alongside p'/moments in the same
    HBM pass (``fused_shift`` / ``fused_store_shift``).
    """

    name: ClassVar[str] = "intdiana"
    fused_local_state: ClassVar[bool] = True  # h_local reads the local image
    alpha_rule: AlphaRule = AlphaDiana()
    bits: int = 32
    stochastic: bool = True
    wire: WireFormat | None = None

    @property
    def fused_capable(self) -> bool:  # type: ignore[override]
        """Delegates to the codec, like IntSGD: the fused route and the
        microbatch pipelining need the wire's fused decode+update kernel."""
        return bool(getattr(self.wire_format, "fused_capable", True))

    @property
    def wire_format(self) -> WireFormat:
        return self.wire if self.wire is not None else DenseInt(bits=self.bits)

    def init(self, params):
        zeros = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), params)
        return {
            "alpha": self.alpha_rule.init(params),
            "h_local": zeros,  # per-worker (lives under the data axes)
            "h_global": zeros,  # replicated
        }

    def observe_update(self, state, dx_stats: DxStats):
        return dict(state, alpha=self.alpha_rule.update(state["alpha"], dx_stats))

    @stages.scoped("alpha")
    def _alphas(self, state, grads, eta, n, dims: TreeDims | None):
        d = dims.d if dims is not None else tree_size(grads)
        a_scalar = self.alpha_rule.alpha(state["alpha"], eta, n, d)
        return jax.tree.map(lambda _: a_scalar, grads)

    def encode_ints(
        self, state, grads, *, key, eta, ctx: CommCtx, dims=None,
        n_accum: int = 1,
    ):
        """One worker's difference image Int(α(g - h_i)) and the α tree.
        Every image carries the FULL local shift: with ``n_accum=M``
        (microbatch pipelining) the accumulated sum is
        Σ_m Int(α(g^m - h_i)) ≈ α(Σ_m g^m - M·h_i), so the 1/(n·M·α)
        decode recovers ḡ - h̄ exactly as the single-shot round does —
        diluting the shift per image (h_i/M) would leave an h̄·(1-1/M)
        bias in ĝ and drift h_local toward M·ḡ. The clip tightens to the
        full n·M sum exactly as for IntSGD. h_local is NOT advanced here —
        that happens in ``aggregate_wire`` (single-shot) or
        ``finish_pipelined`` (accumulated), off the same integer
        image(s)."""
        n = ctx.n
        wf = self.wire_format
        alphas = self._alphas(state, grads, eta, n, dims)
        with stages.stage("encode"):
            akeys = _leaf_keys(fold_worker_key(key, ctx), grads)
            ints = jax.tree.map(
                lambda g, h, a, k: wf.encode(
                    g.astype(jnp.float32) - h, a, k,
                    n_workers=n * n_accum, stochastic=self.stochastic,
                ),
                grads,
                state["h_local"],
                alphas,
                akeys,
            )
        return ints, alphas

    def aggregate_wire(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        """Encode + h_local advance + integer all-reduce, no decode: the
        fused-route entry point (launch/step.py feeds the returned words and
        ``fused_shift(state)`` to the Pallas kernel)."""
        n = ctx.n
        wf = self.wire_format
        ints, alphas = self.encode_ints(
            state, grads, key=key, eta=eta, ctx=ctx, dims=dims
        )
        with stages.stage("counters"):
            max_local = coll.pmax(tree_abs_max(ints), ctx.axes)
        # local shift: h_i += Q(g_i - h_i) = (1/α) Int(α (g_i - h_i))
        with stages.stage("decode"):
            h_local = jax.tree.map(
                lambda h, s, a: h + s.astype(jnp.float32) / a,
                state["h_local"], ints, alphas,
            )
        words_sum, int_sum = ctx.psum_wire(ints, wf)
        with stages.stage("counters"):
            max_int = tree_abs_max(int_sum)
            bits = 1.0 + jnp.ceil(jnp.log2(jnp.maximum(max_int, 1.0) + 1.0))
        return (
            WireAggregate(words=words_sum, ints=int_sum),
            alphas,
            dict(state, h_local=h_local),
            Metrics(max_int, bits, _payload_bytes(wf, grads), max_local),
        )

    def aggregate(self, state, grads, *, key, eta, ctx: CommCtx, dims=None):
        wa, alphas, state, metrics = self.aggregate_wire(
            state, grads, key=key, eta=eta, ctx=ctx, dims=dims
        )
        wf = self.wire_format
        with stages.stage("decode"):
            mean_q = jax.tree.map(
                lambda s, a: wf.decode(s, a, n_workers=ctx.n), wa.ints, alphas
            )
            h_global = jax.tree.map(jnp.add, state["h_global"], mean_q)
        # ĝ = h + mean Q(g_i - h_i) == the advanced global shift
        return h_global, dict(state, h_global=h_global), metrics

    def finish_pipelined(
        self, state, int_sum_acc, local_int_acc, alphas, *, ctx: CommCtx,
        n_accum: int,
    ):
        """Accumulated-image decode + shift advance:
        mean_q = (1/(n·M·α)) ΣΣ ints, h_i += (1/(M·α)) Σ_m ints_i^m,
        ĝ = h_global + mean_q (= new h_global)."""
        wf = self.wire_format
        with stages.stage("decode"):
            h_local = jax.tree.map(
                lambda h, s, a: h + s.astype(jnp.float32) / (n_accum * a),
                state["h_local"], local_int_acc, alphas,
            )
            mean_q = jax.tree.map(
                lambda s, a: wf.decode(s, a, n_workers=ctx.n * n_accum),
                int_sum_acc,
                alphas,
            )
            h_global = jax.tree.map(jnp.add, state["h_global"], mean_q)
        return h_global, dict(state, h_local=h_local, h_global=h_global)

    def fused_shift(self, state):
        return state["h_global"]

    def fused_store_shift(self, state, new_shift):
        return dict(state, h_global=new_shift)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
def with_wire(comp: Compressor, wire) -> Compressor:
    """Rebind a compressor to a wire codec (name string or WireFormat)."""
    wire = make_wire_format(wire)
    fields = {f.name for f in dataclasses.fields(comp)}
    if "wire" not in fields:
        raise ValueError(
            f"compressor {comp.name!r} has no wire-codec seam (only the "
            "integer-wire families are codec-configurable)"
        )
    if "bits" in fields and comp.bits != wire.bits:
        # the codec's width wins in encode(); a silent mismatch would train
        # a different recipe than the compressor name claims
        raise ValueError(
            f"wire codec is {wire.bits}-bit but compressor {comp.name!r} "
            f"was built with bits={comp.bits}; construct them consistently "
            f"(e.g. make_compressor('{comp.name}', bits={wire.bits}, "
            f"wire=...))"
        )
    if "use_kernels" in fields and comp.use_kernels:
        # keep the Pallas routing the compressor asked for: the kernel and
        # jnp encode paths use different (equally valid) stochastic-rounding
        # streams, so silently dropping the flag would change the trajectory
        if dataclasses.is_dataclass(wire):
            if not wire.use_kernels:
                wire = dataclasses.replace(wire, use_kernels=True)
        elif dataclasses.is_dataclass(getattr(wire, "inner", None)):
            # metering wrapper (Logged): propagate into the wrapped codec so
            # the instrumented run meters the SAME trajectory it wraps
            if not wire.inner.use_kernels:
                wire.inner = dataclasses.replace(
                    wire.inner, use_kernels=True
                )
    return dataclasses.replace(comp, wire=wire)


def make_compressor(name: str, **kw) -> Compressor:
    from repro.wire import PackedInt

    reg = {
        "none": NoCompression,
        "allgather_sgd": partial(NoCompression, use_allgather=True),
        "intsgd": IntSGD,
        "intsgd_determ": partial(IntSGD, stochastic=False),
        "intsgd_block": partial(IntSGD, alpha_rule=AlphaBlockwise()),
        "intsgd4": partial(IntSGD, bits=4),
        "intsgd8": partial(IntSGD, bits=8),
        # bit-packed transport words instead of one lane per coordinate
        "intsgd8_packed": partial(IntSGD, bits=8, wire=PackedInt(bits=8)),
        "intsgd4_packed": partial(IntSGD, bits=4, wire=PackedInt(bits=4)),
        "heuristic_intsgd": HeuristicIntSGD,
        "qsgd": QSGD,
        "natsgd": NatSGD,
        "powersgd": PowerSGD,
        "signsgd": SignSGD,
        "topk": TopK,
        "intdiana": IntDIANA,
    }
    if name not in reg:
        raise ValueError(f"unknown compressor {name!r}; options {sorted(reg)}")
    if "wire" in kw and kw["wire"] is not None:
        kw = dict(kw)
        wire = kw.pop("wire")
        return with_wire(reg[name](**kw), wire)  # bits-consistency checked
    return reg[name](**kw)
