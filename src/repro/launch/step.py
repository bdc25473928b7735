"""Train / serve / eval step construction over the production mesh.

One ``shard_map`` per step (via the version-portable layer
:mod:`repro.parallel.collectives`), manual collectives inside (Megatron-JAX
style, replication checks disabled):

  * forward/backward with TP collectives (psum over "model");
  * gradients of REPLICATED params psum'd over "model" (each TP member holds
    a partial contribution);
  * IntSGD (or any baseline compressor) aggregates gradients across the
    data-parallel axes — for the integer-wire families the psum carries ONLY
    the wire codec's transport words (narrow lanes or bit-packed int32
    words, selected via the compressor's ``wire`` field or the ``wire=``
    argument here — see repro.wire), the paper's no-floats contract;
  * optimizer update, routed one of two ways:
      - "zero1": ZeRO-1 update on dp-sharded f32 masters, bf16 param
        all-gather (the default);
      - "fused": the Pallas decode+update kernel family — integer
        dequantization folded into the optimizer step (momentum-SGD or
        bias-corrected AdamW, plus the IntDIANA global-shift add/advance),
        one HBM pass, params updated in place of a master copy; consumes
        the codec's transport words directly (packed words are unpacked
        in-register, never in HBM). Routed by capability
        (Compressor.fused_capable × Optimizer.fused_kernel), never by
        concrete type — see _fused_plan.

  * wire transport is either one monolithic psum (``overlap="off"``, the
    serial reference) or bucketed ``lax.ppermute`` rings
    (``overlap="ring"``) that XLA's scheduler hides behind pending compute;
    with ``microbatches > 1`` the train body encodes and LAUNCHES each
    microbatch's integer image as soon as its backward finishes, so bucket
    k of microbatch i reduces while backward of microbatch i+1 runs. Both
    routes decode bit-identically (integer sums are exact in any order).

Every builder (train / init / serve / eval) resolves the SAME
:class:`Layout` and terminates in the SAME ``collectives.sharded_jit``
pipeline — there is exactly one shard_map+jit construction path.

The first optimization step uses exact (float) aggregation per paper §4.1 —
drivers call the `exact` step once, then the compressed step.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.comm import CommCtx
from repro.core.compressor import (
    Compressor,
    aggregate_exact,
    with_wire,
)
from repro.core.stats import DxStats, TreeDims, scale_dx_stats
from repro.launch import specs as specs_mod
from repro.launch import stages
from repro.models.common import Axes
from repro.models.decode import lm_decode_step, tp_greedy
from repro.models.encdec import (
    encdec_decode_step,
    encdec_loss,
    encode as encdec_encode,
)
from repro.models.transformer import lm_forward, lm_logits_local, lm_loss
from repro.optim import base as optb
from repro.optim.base import Optimizer
from repro.optim.zero1 import (
    own_row,
    zero1_init,
    zero1_state_specs,
    zero1_update,
)
from repro.parallel import collectives as coll
from repro.utils.tree import tree_abs_max
from repro.wire import bucketing


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _replicated_mask(pspecs):
    return jax.tree.map(lambda s: all(p is None for p in s), pspecs)


def _fix_replicated_grads(grads, rep_mask, model_axis):
    """Replicated params receive partial grads on each TP member; sum them."""
    return jax.tree.map(
        lambda g, rep: coll.psum(g, model_axis) if rep else g, grads, rep_mask
    )


def _global_reduce_leaf_sq(leaf_sq, rep_mask, model_axis) -> DxStats:
    """Reduce local per-leaf squared norms to GLOBAL values with ONE psum of
    a stacked vector (TP-sharded leaves summed over "model", replicated
    leaves passed through)."""
    leaves, treedef = jax.tree.flatten(leaf_sq)
    reps = jax.tree.leaves(rep_mask)
    vec = jnp.stack(leaves)
    if model_axis is not None:
        sharded_vec = jnp.where(jnp.asarray(reps), 0.0, vec)
        rep_vec = jnp.where(jnp.asarray(reps), vec, 0.0)
        vec = coll.psum(sharded_vec, model_axis) + rep_vec
    leaf_sq = jax.tree.unflatten(treedef, list(vec))
    return DxStats(sq=jnp.sum(vec), leaf_sq=leaf_sq)


def _global_dx_stats(updates, rep_mask, model_axis) -> DxStats:
    """GLOBAL ||Δx||² from local shards."""
    leaf_sq = jax.tree.map(
        lambda u: jnp.sum(jnp.square(u.astype(jnp.float32))), updates
    )
    return _global_reduce_leaf_sq(leaf_sq, rep_mask, model_axis)


@dataclasses.dataclass
class StepArtifacts:
    """Everything the dry-run / trainer needs for one (arch, shape, mesh)."""

    jitted: Any
    arg_structs: tuple  # ShapeDtypeStructs (global)
    in_shardings: tuple
    out_shardings: Any
    abstract_state: Any  # init-time state structs (for real runs)
    audit_spec: Any = None  # wire_audit.WireSpec declaring the step's
    # (dp axes, codec, n_workers, n_accum) contract — what the static
    # auditor proves the traced jaxpr against


def _zero1_shapes_global(local_state, tp):
    def up(l):
        if l.ndim >= 2:
            return jax.ShapeDtypeStruct((l.shape[0], l.shape[1] * tp), l.dtype)
        return l

    return jax.tree.map(up, local_state)


def _comp_state_shapes(comp: Compressor, cfg, tp, n_dp):
    """Compressor state with a leading dp axis (per-worker state, e.g.
    IntDIANA shifts / EF buffers), via the global/local diff trick."""
    g_params = specs_mod.param_shapes(cfg, tp, 1)
    l_params = specs_mod.param_shapes(cfg, tp, tp)
    gs = jax.eval_shape(comp.init, g_params)
    ls = jax.eval_shape(comp.init, l_params)

    def spec(gl, lo):
        if gl.shape == lo.shape:
            base = [None] * len(gl.shape)
        else:
            diff = [i for i, (a, b) in enumerate(zip(gl.shape, lo.shape)) if a != b]
            base = [None] * len(gl.shape)
            base[diff[0]] = "model"
        return base

    pspecs = jax.tree.map(spec, gs, ls)
    glob = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n_dp,) + x.shape, x.dtype), gs
    )
    return glob, pspecs


def _loss_fn_for(cfg: ModelConfig):
    return encdec_loss if cfg.family == "encdec" else lm_loss


def _fused_state_struct(base_opt: Optimizer, shapes):
    """ShapeDtypeStructs of the fused-route optimizer state for ``shapes``
    (f32 tensor per param per FUSED_STATE_TENSORS entry + int32 scalars)."""
    kern = base_opt.fused_kernel
    st = {
        nm: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), shapes
        )
        for nm in optb.FUSED_STATE_TENSORS[kern]
    }
    for nm in optb.FUSED_STATE_SCALARS[kern]:
        st[nm] = jax.ShapeDtypeStruct((), jnp.int32)
    return st


def _fused_state_specs(base_opt: Optimizer, pspecs):
    kern = base_opt.fused_kernel
    specs = {nm: pspecs for nm in optb.FUSED_STATE_TENSORS[kern]}
    for nm in optb.FUSED_STATE_SCALARS[kern]:
        specs[nm] = P()
    return specs


# ---------------------------------------------------------------------------
# layout resolution — ONE place derives (tp, dp, specs) for every builder
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Layout:
    """Resolved execution layout of (cfg, mesh): axes, specs and masks the
    train / init / serve / eval builders all share."""

    cfg: ModelConfig
    mesh: Any
    tp: int
    dp: tuple  # data-parallel (gradient-sync) axis names
    dp_sizes: tuple
    n_dp: int
    axes: Axes  # model-code axis handles (TP)
    ctx: CommCtx  # compressor communication context
    pspecs: Any  # param PartitionSpecs
    rep_mask: Any  # which param leaves are TP-replicated
    g_shapes: Any  # global param ShapeDtypeStructs (param_dtype)
    l_shapes: Any  # local param ShapeDtypeStructs (param_dtype)
    dims: TreeDims  # global model dimensionality (α's d)

    @property
    def dp_spec(self):
        return coll.axis_spec(self.dp)

    @property
    def model_axis(self) -> Optional[str]:
        return "model" if self.tp > 1 else None


def resolve_layout(
    cfg: ModelConfig,
    mesh,
    *,
    param_dtype=jnp.bfloat16,
    tp_override: Optional[int] = None,
    remap_tp1: bool = False,
    overlap: str = "off",
    bucket_words: int = bucketing.DEFAULT_BUCKET_WORDS,
) -> Layout:
    """Derive the layout. With ``remap_tp1`` (train path), a tp==1 override
    turns the whole mesh data-parallel: the model is replicated and IntSGD
    aggregates over every chip. ``overlap``/``bucket_words`` configure the
    wire transport on the resulting CommCtx ("off" = one monolithic psum,
    "ring" = bucketed ppermute rings XLA can hide behind compute)."""
    tp = tp_override if tp_override is not None else mesh.shape["model"]
    if remap_tp1 and tp == 1:
        dp = tuple(mesh.axis_names)
    else:
        dp = coll.dp_axes_of(mesh)
    dp_sizes = tuple(mesh.shape[a] for a in dp)
    n_dp = 1
    for s in dp_sizes:
        n_dp *= s
    axes = Axes(tp="model", tp_size=tp) if tp > 1 else Axes()
    ctx = CommCtx(
        axes=dp, axis_sizes=dp_sizes, model_axis="model",
        overlap=overlap, bucket_words=bucket_words,
    )
    g_shapes, l_shapes, pspecs = specs_mod.infer_param_specs(cfg, tp)
    cast = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, param_dtype), t
    )
    return Layout(
        cfg=cfg,
        mesh=mesh,
        tp=tp,
        dp=dp,
        dp_sizes=dp_sizes,
        n_dp=n_dp,
        axes=axes,
        ctx=ctx,
        pspecs=pspecs,
        rep_mask=_replicated_mask(pspecs),
        g_shapes=cast(g_shapes),
        l_shapes=cast(l_shapes),
        dims=specs_mod.global_tree_dims(cfg, tp),
    )


def _sharded(layout: Layout, body, in_specs, out_specs, *, donate=(),
             shard_outputs=True):
    """The single shard_map+jit pipeline every builder terminates in."""
    return coll.sharded_jit(
        body,
        layout.mesh,
        in_specs,
        out_specs,
        donate=donate,
        shard_outputs=shard_outputs,
    )


# ---------------------------------------------------------------------------
# shared step-body stages
# ---------------------------------------------------------------------------
@stages.scoped("fwd_bwd")
def _forward_backward(layout: Layout, loss_fn, params, batch):
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, layout.axes, layout.cfg, dtype=jnp.bfloat16)
    )(params)
    if layout.tp > 1:
        grads = _fix_replicated_grads(grads, layout.rep_mask, "model")
    return loss, grads


def _unstack_comp(comp_state):
    return jax.tree.map(lambda x: x[0] if x.ndim >= 1 else x, comp_state)


def _restack_comp(cs, comp_state_like):
    new = jax.tree.map(lambda x: x[None] if x.ndim >= 0 else x, cs)
    return jax.tree.map(
        lambda x, like: x.reshape(like.shape), new, comp_state_like
    )


@stages.scoped("alpha")
def _observe_dx(layout: Layout, compressor, base_opt, cs, new_params, params):
    """Δx stats -> α rule, rescaled to gradient-equivalent units
    (base_opt.dx_scale — §4.1 momentum correction)."""
    delta = jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        new_params,
        params,
    )
    dx_stats = _global_dx_stats(delta, layout.rep_mask, layout.model_axis)
    return compressor.observe_update(
        cs, scale_dx_stats(dx_stats, base_opt.dx_scale)
    )


def _fused_plan(base_opt: Optimizer, compressor: Compressor) -> str:
    """Validate the (compressor × optimizer) pair against the fused-route
    capability contract and return the kernel name. No type-gates: the
    compressor advertises wire-level aggregation via ``fused_capable``, the
    optimizer its Pallas decode+update kernel via ``Optimizer.fused_kernel``
    — any capable pair routes, any other names the missing capability."""
    if not getattr(compressor, "fused_capable", False):
        wf = getattr(compressor, "wire_format", None)
        if wf is not None and not getattr(wf, "fused_capable", True):
            raise ValueError(
                "fused update routing consumes the summed transport words "
                f"directly, but wire codec {wf.name!r} has no fused "
                "decode+update kernel (WireFormat.fused_capable): its "
                f"gather-transport payload (planes "
                f"{getattr(wf, 'plane_names', ())!r}) needs a scatter-shaped "
                "decode — use a psum-transport codec (dense/packed) or "
                "fused=False"
            )
        raise ValueError(
            "fused update routing consumes the summed transport words "
            "directly, which needs wire-level aggregation "
            f"(Compressor.fused_capable); compressor {compressor.name!r} "
            "does not advertise it — use an integer-wire compressor or "
            "fused=False"
        )
    if base_opt.fused_kernel is None or base_opt.hyper is None:
        raise ValueError(
            "fused update routing needs an optimizer exposing a fused "
            "decode+update kernel (Optimizer.fused_kernel); "
            f"kind={base_opt.kind!r} advertises none — use optim.sgd "
            "(heavy-ball) or optim.adamw, or fused=False"
        )
    return base_opt.fused_kernel


@stages.scoped("clip")
def _clip_factor(layout: Layout, clip_norm, *, ghat=None, int_sum=None,
                 alphas=None, shift=None):
    """Global-norm gradient clip factor min(1, c/||ĝ||). For the fused
    integer route ||ĝ||² is computed straight off the wire payload, decoded
    in-register exactly as WireFormat.decode does (ĝ = Σints·(1/(nα)), plus
    the replicated shift h for IntDIANA), so ĝ is never materialized — the
    elementwise decode fuses into the reduction — and both routes clip by
    the same factor."""
    if int_sum is not None:
        n = layout.ctx.n
        if shift is None:
            leaf_sq = jax.tree.map(
                lambda s, a: jnp.sum(
                    jnp.square(s.astype(jnp.float32) * (1.0 / (n * a)))
                ),
                int_sum,
                alphas,
            )
        else:
            leaf_sq = jax.tree.map(
                lambda s, a, h: jnp.sum(
                    jnp.square(h + s.astype(jnp.float32) * (1.0 / (n * a)))
                ),
                int_sum,
                alphas,
                shift,
            )
    else:
        leaf_sq = jax.tree.map(
            lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))), ghat
        )
    sq = _global_reduce_leaf_sq(leaf_sq, layout.rep_mask, layout.model_axis).sq
    return jnp.minimum(1.0, clip_norm / (jnp.sqrt(sq) + 1e-12))


def _microbatch(batch, m: int, n_micro: int):
    """Static slice m of n_micro along the (local) batch dim of every leaf."""
    def one(v):
        b = v.shape[0] // n_micro
        return v[m * b : (m + 1) * b]

    return jax.tree.map(one, batch)


def _pipelined_grad_stage(
    layout: Layout, loss_fn, compressor: Compressor, cs, params, batch, akey,
    eta, n_micro: int,
):
    """Microbatch/grad-accum wire pipelining: encode microbatch i's integer
    image and LAUNCH its (bucketed) all-reduce immediately, then start
    backward of microbatch i+1 — the data dependencies leave bucket k of
    image i free to ride the wire while compute i+1 runs, which is exactly
    the overlap XLA's latency-hiding scheduler exploits on the ring route.

    Math: each microbatch image is clipped for the FULL n·M accumulated sum
    (``encode_ints(n_accum=M)`` — so the int32 accumulator can never wrap,
    even on a 32-bit wire with clip-saturating gradients) and reduced
    separately; the M summed images then add exactly, so

        ghat = (1/(n·M·α)) Σ_m Σ_i Int(α g_i^m)

    is the mean of M independent estimates (for IntDIANA each image carries
    the difference g^m - h_i/M, so the mean estimates g - h_i) — the same
    estimator whether the transport is the serial psum or the bucketed
    rings (parity is pinned by tests/test_overlap.py). Decode + compressor
    state advance happen in ``compressor.finish_pipelined``; compressors
    whose state reads the LOCAL integer image (``fused_local_state``, e.g.
    IntDIANA's h_local) get the local accumulation too."""
    track_local = compressor.fused_local_state
    wf = compressor.wire_format
    loss_acc = jnp.zeros(())
    max_int = jnp.zeros(())
    int_acc = local_acc = alphas = None
    for m in range(n_micro):
        mb = _microbatch(batch, m, n_micro)
        loss_m, grads_m = _forward_backward(layout, loss_fn, params, mb)
        ints_m, alphas = compressor.encode_ints(
            cs, grads_m, key=jax.random.fold_in(akey, m), eta=eta,
            ctx=layout.ctx, dims=layout.dims, n_accum=n_micro,
        )
        if track_local:
            local_acc = (
                ints_m if local_acc is None
                else jax.tree.map(jnp.add, local_acc, ints_m)
            )
        # the reduce of image m is issued HERE, before backward of m+1 —
        # no result of it is needed until the decode after the loop
        _, int_sum_m = layout.ctx.psum_wire(ints_m, wf)
        int_acc = (
            int_sum_m if int_acc is None
            else jax.tree.map(jnp.add, int_acc, int_sum_m)
        )
        # wire-width metric: what each psum actually carried, not the
        # M-fold accumulated sum
        with stages.stage("counters"):
            max_int = jnp.maximum(max_int, tree_abs_max(int_sum_m))
        loss_acc = loss_acc + loss_m
    ghat, cs = compressor.finish_pipelined(
        cs, int_acc, local_acc, alphas, ctx=layout.ctx, n_accum=n_micro
    )
    with stages.stage("counters"):
        bits = 1.0 + jnp.ceil(jnp.log2(jnp.maximum(max_int, 1.0) + 1.0))
    return ghat, cs, loss_acc / n_micro, (max_int, bits)


def _accum_grad_stage(layout: Layout, loss_fn, params, batch, n_micro: int):
    """Plain gradient accumulation (exact step / non-IntSGD compressors):
    mean of the microbatch gradients in f32, one aggregation afterwards."""
    loss_acc = jnp.zeros(())
    g_acc = None
    for m in range(n_micro):
        mb = _microbatch(batch, m, n_micro)
        loss_m, grads_m = _forward_backward(layout, loss_fn, params, mb)
        with stages.stage("fwd_bwd"):
            g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads_m)
            g_acc = g32 if g_acc is None else jax.tree.map(jnp.add, g_acc, g32)
        loss_acc = loss_acc + loss_m
    with stages.stage("fwd_bwd"):
        grads = jax.tree.map(lambda g: g / n_micro, g_acc)
    return loss_acc / n_micro, grads


def _make_train_body(
    layout: Layout,
    *,
    loss_fn,
    compressor: Compressor,
    base_opt: Optimizer,
    lr_schedule: Callable,
    param_dtype,
    exact: bool,
    update_route: str,  # "zero1" | "fused"
    clip_norm: Optional[float] = None,
    microbatches: int = 1,
):
    """The ONE train/optimize step body, parameterized by (loss, compressor,
    optimizer, fused-kernel routing, clipping, microbatch pipelining). All
    jitted train variants are built from it."""
    if update_route == "fused":
        _fused_plan(base_opt, compressor)
    # the microbatch wire pipelining rides the SAME capability as the fused
    # route: compressors advertising wire-level aggregation (encode_ints /
    # finish_pipelined) pipeline their integer images; everything else gets
    # plain f32 gradient accumulation
    pipelined = microbatches > 1 and compressor.fused_capable

    def step(params, opt_state, comp_state, step_idx, key, batch):
        eta = lr_schedule(step_idx)
        cs = _unstack_comp(comp_state)
        wa = alphas = None
        akey = jax.random.fold_in(key, 1)
        m_axes = layout.dp + (("model",) if layout.tp > 1 else ())
        if not exact and pipelined:
            ghat, cs, loss, (max_int, bits) = _pipelined_grad_stage(
                layout, loss_fn, compressor, cs, params, batch, akey, eta,
                microbatches,
            )
            with stages.stage("counters"):
                metrics = (coll.pmax(max_int, m_axes), coll.pmax(bits, m_axes))
        else:
            if microbatches > 1:
                loss, grads = _accum_grad_stage(
                    layout, loss_fn, params, batch, microbatches
                )
            else:
                loss, grads = _forward_backward(layout, loss_fn, params, batch)
            if exact:
                ghat = aggregate_exact(grads, layout.ctx)
                metrics = (jnp.zeros(()), jnp.zeros(()))
            else:
                if update_route == "fused":
                    wa, alphas, cs, m = compressor.aggregate_wire(
                        cs, grads, key=akey, eta=eta, ctx=layout.ctx,
                        dims=layout.dims,
                    )
                    ghat = None
                else:
                    ghat, cs, m = compressor.aggregate(
                        cs, grads, key=akey, eta=eta, ctx=layout.ctx,
                        dims=layout.dims,
                    )
                with stages.stage("counters"):
                    metrics = (
                        coll.pmax(m.max_int, m_axes),
                        coll.pmax(m.bits_per_coord, m_axes),
                    )

        # replicated global shift the fused decode must add (IntDIANA's
        # h_global; None for shift-free compressors)
        shift = compressor.fused_shift(cs) if wa is not None else None
        clip_scale = jnp.float32(1.0)
        if clip_norm is not None:
            scale = _clip_factor(
                layout, clip_norm, ghat=ghat,
                int_sum=None if wa is None else wa.ints, alphas=alphas,
                shift=shift,
            )
            if ghat is not None:
                with stages.stage("clip"):
                    ghat = jax.tree.map(lambda g: g * scale, ghat)
            else:  # fused: the clip rides the kernels' scalar vector
                clip_scale = scale

        if update_route == "fused":
            new_params, new_opt, new_shift = _fused_update_stage(
                layout, params, opt_state, eta, base_opt,
                ghat=ghat, wire_agg=wa, alphas=alphas,
                wf=compressor.wire_format, clip_scale=clip_scale,
                shift=shift,
            )
            if new_shift is not None:
                cs = compressor.fused_store_shift(cs, new_shift)
        else:
            new_params, new_opt = zero1_update(
                base_opt,
                opt_state,
                ghat,
                eta,
                dp_axes=layout.dp,
                dp_index=layout.ctx.worker_index(),
                n_dp=layout.n_dp,
                param_dtype=param_dtype,
                params_like=params,
            )
        cs = _observe_dx(layout, compressor, base_opt, cs, new_params, params)
        new_comp = _restack_comp(cs, comp_state)
        loss_g = coll.psum(loss, layout.dp) / layout.n_dp
        return new_params, new_opt, new_comp, loss_g, metrics

    return step


@stages.scoped("update")
def _fused_update_stage(layout: Layout, params, opt_state, eta,
                        base_opt: Optimizer, *, ghat, wire_agg, alphas, wf,
                        clip_scale, shift):
    """Pallas fused dequantize+optimizer route: one HBM pass per leaf,
    params updated directly (no ZeRO master shard). The update consumes the
    summed TRANSPORT WORDS exactly as they left the all-reduce — for the
    packed codec the integer image is never materialized; the kernel unpacks
    fields in-register (wf.fused_update dispatch on
    ``base_opt.fused_kernel``). With a shift tree (IntDIANA) the kernel also
    emits the advanced global shift in the same pass. The exact (step-0)
    path has no integer payload and runs the same arithmetic unfused
    (optim.base.fused_reference_update).

    Returns ``(new_params, new_opt_state, new_shift | None)``."""
    if wire_agg is None:  # exact aggregation path
        new_params, new_opt = optb.fused_reference_update(
            base_opt, ghat, params, opt_state, eta
        )
        return new_params, new_opt, None

    kern = base_opt.fused_kernel
    tail, new_scalars = optb.fused_step_scalars(base_opt, opt_state, eta)
    tensor_names = optb.FUSED_STATE_TENSORS[kern]
    n = layout.ctx.n

    p_leaves, treedef = jax.tree.flatten(params)
    w_leaves = treedef.flatten_up_to(wire_agg.words)
    a_leaves = treedef.flatten_up_to(alphas)
    s_leaves = (
        treedef.flatten_up_to(shift) if shift is not None
        else [None] * len(p_leaves)
    )
    state_leaves = [treedef.flatten_up_to(opt_state[nm]) for nm in tensor_names]

    new_p, new_h = [], []
    new_state = [[] for _ in tensor_names]
    for i, (p, w, a, h) in enumerate(zip(p_leaves, w_leaves, a_leaves, s_leaves)):
        scalars = jnp.stack([1.0 / (n * a), clip_scale, *tail])
        po, oo, ho = wf.fused_update(
            w, p, tuple(sl[i] for sl in state_leaves), scalars,
            kernel=kern, n_summed=n, shift=h,
        )
        new_p.append(po)
        new_h.append(ho)
        for acc, o in zip(new_state, oo):
            acc.append(o)

    unflat = lambda leaves: jax.tree.unflatten(treedef, leaves)
    new_opt = {nm: unflat(ls) for nm, ls in zip(tensor_names, new_state)}
    new_opt.update(new_scalars)
    return (
        unflat(new_p),
        new_opt,
        unflat(new_h) if shift is not None else None,
    )


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------
def build_train_step(
    cfg: ModelConfig,
    mesh,
    shape: ShapeConfig,
    *,
    compressor: Compressor,
    base_opt: Optimizer,
    lr_schedule: Callable,
    param_dtype=jnp.bfloat16,
    exact_first: bool = False,
    donate: bool = True,
    tp_override: Optional[int] = None,
    fused: bool = False,
    clip_norm: Optional[float] = None,
    wire=None,
    overlap: str = "off",
    bucket_words: int = bucketing.DEFAULT_BUCKET_WORDS,
    microbatches: int = 1,
    verify: Optional[str] = None,
) -> StepArtifacts:
    from repro.launch.inputs import input_specs

    if verify not in (None, "static"):
        raise ValueError(f"verify must be None or 'static', got {verify!r}")

    if wire is not None:
        # config-level codec selection: rebind the compressor's transport
        # (accepts a repro.wire registry name or a WireFormat instance)
        compressor = with_wire(compressor, wire)
    if microbatches > 1 and fused:
        raise ValueError(
            "microbatch pipelining accumulates summed integer images, which "
            "the fused packed-word kernel cannot consume; use the zero1 "
            "route (fused=False) with microbatches > 1"
        )
    if microbatches < 1:
        raise ValueError(f"microbatches must be >= 1, got {microbatches}")
    layout = resolve_layout(
        cfg, mesh, param_dtype=param_dtype, tp_override=tp_override,
        remap_tp1=True, overlap=overlap, bucket_words=bucket_words,
    )
    if microbatches > 1:
        local_batch = shape.global_batch // layout.n_dp
        if local_batch % microbatches:
            raise ValueError(
                f"local batch {local_batch} (global {shape.global_batch} over "
                f"{layout.n_dp} workers) is not divisible into "
                f"{microbatches} microbatches"
            )
    loss_fn = _loss_fn_for(cfg)

    if fused:
        _fused_plan(base_opt, compressor)  # fail at build time, not trace
        opt_local = _fused_state_struct(base_opt, layout.l_shapes)
        opt_global = _fused_state_struct(base_opt, layout.g_shapes)
        opt_specs = _fused_state_specs(base_opt, layout.pspecs)
    else:
        opt_local = jax.eval_shape(
            partial(zero1_init, base_opt, n_dp=layout.n_dp), layout.l_shapes
        )
        opt_global = _zero1_shapes_global(opt_local, layout.tp)
        opt_specs = zero1_state_specs(
            opt_local, layout.dp_spec, model_axis=layout.model_axis
        )
    comp_global, comp_leaf_specs = _comp_state_shapes(
        compressor, cfg, layout.tp, layout.n_dp
    )
    comp_specs = jax.tree.map(
        lambda x, base: P(*([layout.dp_spec] + list(base))),
        comp_global,
        comp_leaf_specs,
    )

    batch_struct = input_specs(cfg, shape, kind="train")
    batch_specs = specs_mod.batch_pspecs(batch_struct, dp=layout.dp)

    in_specs = (
        layout.pspecs,
        opt_specs,
        comp_specs,
        P(),
        P(),
        batch_specs,
    )
    out_specs = (layout.pspecs, opt_specs, comp_specs, P(), (P(), P()))

    def make(exact):
        body = _make_train_body(
            layout,
            loss_fn=loss_fn,
            compressor=compressor,
            base_opt=base_opt,
            lr_schedule=lr_schedule,
            param_dtype=param_dtype,
            exact=exact,
            update_route="fused" if fused else "zero1",
            clip_norm=clip_norm,
            microbatches=microbatches,
        )
        return _sharded(
            layout, body, in_specs, out_specs,
            donate=(0, 1, 2) if donate else (),
        )

    arg_structs = (
        layout.g_shapes,
        opt_global,
        comp_global,
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        batch_struct,
    )
    # declare the wire contract the static auditor proves the trace against
    # (float-wire baselines like NoCompression have no codec and no spec).
    # n_accum is the number of IMAGES that ride the wire per step: M for the
    # pipelined body, but 1 when the compressor cannot pipeline (not
    # fused_capable — e.g. a gather-transport codec), because that body
    # accumulates float grads and aggregates once.
    wf = getattr(compressor, "wire_format", None)
    if wf is not None:
        from repro.analysis.wire_audit import spec_for_step

        n_images = microbatches if compressor.fused_capable else 1
        audit_spec = spec_for_step(
            layout, wf, n_accum=n_images, fused=fused
        )
    else:
        audit_spec = None
    artifacts = StepArtifacts(
        jitted={"compressed": make(False), "exact": make(True)},
        arg_structs=arg_structs,
        in_shardings=coll.named_shardings(mesh, in_specs),
        out_shardings=coll.named_shardings(mesh, out_specs),
        abstract_state=None,
        audit_spec=audit_spec,
    )
    if verify == "static":
        if audit_spec is None:
            raise ValueError(
                "verify='static' needs an integer wire to prove; "
                f"compressor {type(compressor).__name__} has no wire_format"
            )
        from repro.analysis.schedule import verify_step

        verify_step(artifacts).raise_if_failed()
    return artifacts


def build_init_state(
    cfg: ModelConfig,
    mesh,
    *,
    compressor: Compressor,
    base_opt: Optimizer,
    fused: bool = False,
):
    """jitted (global params) -> (opt_state, comp_state) with correct
    optimizer layout — ZeRO-1 masters (== initial params) by default, a
    replicated f32 momentum tree for the fused route — and dp-stacked
    compressor state."""
    layout = resolve_layout(cfg, mesh, param_dtype=jnp.float32)
    comp_global, comp_leaf_specs = _comp_state_shapes(
        compressor, cfg, layout.tp, layout.n_dp
    )
    comp_specs = jax.tree.map(
        lambda x, base: P(*([layout.dp_spec] + list(base))),
        comp_global,
        comp_leaf_specs,
    )

    if fused:
        _fused_plan(base_opt, compressor)
        opt_specs = _fused_state_specs(base_opt, layout.pspecs)

        def init_fn(params):
            opt_state = optb.fused_state_init(base_opt, params)
            cs = compressor.init(params)
            cs = jax.tree.map(lambda x: jnp.asarray(x)[None], cs)
            return opt_state, cs

    else:
        l_params_f32 = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
            layout.l_shapes,
        )
        opt_local = jax.eval_shape(
            partial(zero1_init, base_opt, n_dp=layout.n_dp), l_params_f32
        )
        opt_specs = zero1_state_specs(
            opt_local, layout.dp_spec, model_axis=layout.model_axis
        )

        def init_fn(params):
            dp_index = layout.ctx.worker_index()
            my = jax.tree.map(
                lambda p: own_row(p, layout.n_dp, dp_index)[None], params
            )
            base_state = base_opt.init(jax.tree.map(lambda m: m[0], my))
            restack = lambda t: jax.tree.map(
                lambda x: x[None] if x.ndim >= 1 else x, t
            )
            opt_state = {"master": my, "base": restack(base_state)}
            cs = compressor.init(params)
            cs = jax.tree.map(lambda x: jnp.asarray(x)[None], cs)
            return opt_state, cs

    return _sharded(
        layout, init_fn, (layout.pspecs,), (opt_specs, comp_specs)
    )


# ---------------------------------------------------------------------------
# eval step (loss-only — validation / perplexity sweeps)
# ---------------------------------------------------------------------------
def build_eval_step(
    cfg: ModelConfig,
    mesh,
    shape: ShapeConfig,
    *,
    param_dtype=jnp.bfloat16,
) -> StepArtifacts:
    """Forward-only loss over the mesh: the train body's forward stage with
    aggregation/update routing stripped."""
    from repro.launch.inputs import input_specs

    layout = resolve_layout(
        cfg, mesh, param_dtype=param_dtype, remap_tp1=True
    )
    loss_fn = _loss_fn_for(cfg)

    batch_struct = input_specs(cfg, shape, kind="train")
    batch_specs = specs_mod.batch_pspecs(batch_struct, dp=layout.dp)

    def body(params, batch):
        loss = loss_fn(params, batch, layout.axes, layout.cfg, dtype=jnp.bfloat16)
        return coll.psum(loss, layout.dp) / layout.n_dp

    in_specs = (layout.pspecs, batch_specs)
    jitted = _sharded(layout, body, in_specs, P())
    return StepArtifacts(
        jitted={"eval": jitted},
        arg_structs=(layout.g_shapes, batch_struct),
        in_shardings=coll.named_shardings(mesh, in_specs),
        out_shardings=None,
        abstract_state=None,
    )


# ---------------------------------------------------------------------------
# serve steps (prefill / decode)
# ---------------------------------------------------------------------------
def build_serve_step(
    cfg: ModelConfig,
    mesh,
    shape: ShapeConfig,
    *,
    param_dtype=jnp.bfloat16,
) -> StepArtifacts:
    from repro.launch.inputs import input_specs

    layout = resolve_layout(cfg, mesh, param_dtype=param_dtype)
    dp, dp_sizes, n_dp, tp = layout.dp, layout.dp_sizes, layout.n_dp, layout.tp
    seq_sharded = shape.kind == "decode" and shape.global_batch < n_dp
    if seq_sharded:
        axes = Axes(tp="model", tp_size=tp, sp=dp, sp_sizes=dp_sizes)
        b_local = shape.global_batch
        s_local = shape.seq_len // n_dp
    else:
        axes = Axes(tp="model", tp_size=tp)
        b_local = max(1, shape.global_batch // n_dp)
        s_local = shape.seq_len

    if shape.kind == "prefill":
        batch_struct = input_specs(cfg, shape, kind="prefill")
        batch_specs = specs_mod.batch_pspecs(batch_struct, dp=dp)

        def prefill(params, batch):
            if cfg.family == "encdec":
                h = encdec_encode(params, batch["frames"], axes, cfg)
                logits = jnp.einsum(
                    "btd,dv->btv", h[:, -1:], params["lm_head"].astype(h.dtype)
                ).astype(jnp.float32)[:, 0]
            else:
                h = lm_forward(params, batch, axes, cfg)
                logits = lm_logits_local(params, h[:, -1:], cfg)[:, 0]
            return logits

        in_specs = (layout.pspecs, batch_specs)
        out_specs = P(layout.dp_spec, "model")
        jitted = _sharded(
            layout, prefill, in_specs, out_specs, shard_outputs=False
        )
        arg_structs = (layout.g_shapes, batch_struct)
        return StepArtifacts(
            jitted={"prefill": jitted},
            arg_structs=arg_structs,
            in_shardings=coll.named_shardings(mesh, in_specs),
            out_shardings=None,
            abstract_state=None,
        )

    # decode
    cache_local = specs_mod.cache_shapes(
        cfg, tp, tp, b_local, s_local, s_src=min(shape.seq_len, 32768)
    )
    cache_specs = specs_mod.cache_pspecs(
        cache_local, dp=dp, seq_sharded=seq_sharded
    )

    def to_global(struct, spec):
        shape_l = list(struct.shape)
        for i, p in enumerate(spec):
            if p is None:
                continue
            size = tp if p == "model" else n_dp
            shape_l[i] = shape_l[i] * size
        return jax.ShapeDtypeStruct(tuple(shape_l), struct.dtype)

    cache_global = jax.tree.map(
        to_global, cache_local, cache_specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )

    tok_struct = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
    pos_struct = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
    tok_spec = P() if seq_sharded else P(layout.dp_spec)

    def decode(params, cache, tokens, pos):
        if cfg.family == "encdec":
            logits, new_cache = encdec_decode_step(
                params, cache, tokens, pos, axes, cfg
            )
        else:
            logits, new_cache = lm_decode_step(params, cache, tokens, pos, axes, cfg)
        next_tok = tp_greedy(logits, axes)
        return next_tok, new_cache

    in_specs = (layout.pspecs, cache_specs, tok_spec, tok_spec)
    out_specs = (tok_spec, cache_specs)
    jitted = _sharded(layout, decode, in_specs, out_specs, donate=(1,))
    arg_structs = (layout.g_shapes, cache_global, tok_struct, pos_struct)
    return StepArtifacts(
        jitted={"decode": jitted},
        arg_structs=arg_structs,
        in_shardings=coll.named_shardings(mesh, in_specs),
        out_shardings=coll.named_shardings(mesh, out_specs),
        abstract_state=None,
    )
