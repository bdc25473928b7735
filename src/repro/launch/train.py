"""Training driver: end-to-end loop with checkpointing, fault tolerance and
elastic re-mesh.

CLI (CPU-scale demo; the same builder lowers for the production mesh in
dryrun.py):

  PYTHONPATH=src python -m repro.launch.train \\
      --arch granite-8b --smoke --steps 50 --compressor intsgd \\
      --ckpt-dir /tmp/ckpt [--resume] [--data 2 --model 2] \\
      [--profile-dir /tmp/prof]

The loop names what the host does in profiler spans: ``data`` (the batch
made and placed), ``step`` (the call), ``loss_read`` (the wait for the
loss) and ``checkpoint``. With a profile directory it records steps
start+5 .. start+9 there, the device's operations under the stage names of
``launch/stages.py`` beside these spans. The log's ``dt`` is the interval
between successive loss reads.
"""
from __future__ import annotations

import argparse
import math
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointStore
from repro.configs import ShapeConfig, get_arch, smoke_config
from repro.core import make_compressor, with_wire
from repro.data.synthetic import SyntheticLMData
from repro.kernels.ops import fused_view
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.step import build_init_state, build_train_step
from repro.models.transformer import init_lm_params
from repro.optim import adamw, sgd
from repro.optim.base import FUSED_STATE_TENSORS
from repro.optim.schedules import constant, warmup_wrap
from repro.optim.zero1 import zero1_view
from repro.parallel.collectives import mesh_from_counts
from repro.wire import PackedInt, wire_format_names
from repro.wire.bucketing import DEFAULT_BUCKET_WORDS

# steps start+5 .. start+9 are profiled: past the compiles and the warmup
PROFILE_FIRST, PROFILE_STEPS = 5, 5


def _view_line(name, shapes, view, other: str) -> str:
    """How many parameters of leaves of `shapes` `view` gives the "native"
    view, and how many leaves it gives the `other` one."""
    native = total = others = 0
    for shape in shapes:
        size = math.prod(shape)
        total += size
        if view(shape) == "native":
            native += size
        else:
            others += 1
    return (f"[train] {name} view: {native} of {total} in place, "
            f"{others} leaves {other}")


def fused_view_line(shapes, *, bits: int, n_tensors: int) -> str:
    """How many parameters of leaves of `shapes` the packed fused kernel
    updates in their own layout, and how many leaves it pads
    (``kernels.ops.fused_view``)."""
    return _view_line(
        "fused", shapes,
        lambda s: fused_view(s, bits=bits, n_tensors=n_tensors), "padded")


def zero1_view_line(shapes, *, n_dp: int, tp: int) -> str:
    """How many parameters of local leaves of `shapes` ZeRO-1 holds as
    blocks of the leaf's own rows, and how many leaves it holds flat
    (``optim.zero1.zero1_view``)."""
    return _view_line(
        "zero1", shapes, lambda s: zero1_view(s, n_dp, tp), "flat")


def train_loop(
    cfg,
    mesh,
    shape,
    *,
    compressor,
    steps: int,
    lr: float = 0.3,
    ckpt: CheckpointStore | None = None,
    ckpt_every: int = 20,
    resume: bool = False,
    param_dtype=jnp.float32,
    log_every: int = 5,
    seed: int = 0,
    fused: bool = False,
    clip_norm: float | None = 1.0,
    wire: str | None = None,
    overlap: str = "off",
    bucket_words: int = DEFAULT_BUCKET_WORDS,
    microbatches: int = 1,
    opt: str = "sgd",
    profile_dir: str | None = None,
):
    comp = make_compressor(compressor)
    if wire is not None:
        comp = with_wire(comp, wire)
    opts = {
        "sgd": lambda: sgd(momentum=0.9, weight_decay=1e-4),
        "adamw": lambda: adamw(weight_decay=1e-4),
    }
    opt = opts[opt]()
    sched = warmup_wrap(constant(lr), 5)
    art = build_train_step(
        cfg, mesh, shape, compressor=comp, base_opt=opt,
        lr_schedule=sched, param_dtype=param_dtype,
        fused=fused, clip_norm=clip_norm,
        overlap=overlap, bucket_words=bucket_words, microbatches=microbatches,
    )
    tp = mesh.shape["model"]
    n_dp = mesh.size // tp
    key = jax.random.PRNGKey(seed)

    start = 0
    if resume and ckpt and ckpt.latest_step() is not None:
        structs = {"params": art.arg_structs[0], "opt": art.arg_structs[1],
                   "comp": art.arg_structs[2]}
        shardings = {"params": art.in_shardings[0], "opt": art.in_shardings[1],
                     "comp": art.in_shardings[2]}
        state, extra, start = ckpt.restore(structs, shardings=shardings)
        params, opt_state, comp_state = state["params"], state["opt"], state["comp"]
        print(f"[train] resumed from step {start}")
    else:
        params = init_lm_params(key, cfg, tp=tp, n_shards=1, dtype=param_dtype)
        params = jax.device_put(params, art.in_shardings[0])
        init = build_init_state(
            cfg, mesh, compressor=comp, base_opt=opt, fused=fused
        )
        opt_state, comp_state = init(params)
    local = [sh.shard_shape(st.shape) for st, sh in zip(
        jax.tree.leaves(art.arg_structs[0]),
        jax.tree.leaves(art.in_shardings[0]), strict=True)]
    if not fused:
        print(zero1_view_line(local, n_dp=n_dp, tp=tp))
    elif isinstance(comp.wire_format, PackedInt):
        n_tensors = (1 + len(FUSED_STATE_TENSORS[opt.fused_kernel])
                     + (comp.fused_shift(comp_state) is not None))
        print(fused_view_line(
            local, bits=comp.wire_format.bits, n_tensors=n_tensors))

    data = SyntheticLMData(
        cfg.vocab, shape.seq_len, shape.global_batch, seed=seed
    )
    batch_sharding = art.in_shardings[5]

    losses = []
    profiled = range(start + PROFILE_FIRST, start + PROFILE_FIRST + PROFILE_STEPS)
    tracing = False
    last_read = time.perf_counter()
    try:
        for i in range(start, steps):
            if profile_dir and i == profiled.start:
                jax.profiler.start_trace(profile_dir)
                tracing = True
            with jax.profiler.TraceAnnotation("data"):
                batch = data.batch(i, 0)  # global batch; sharded by device_put
                batch = {k: jax.device_put(v, batch_sharding[k])
                         for k, v in batch.items()}
            fn = art.jitted["exact"] if i == 0 else art.jitted["compressed"]
            with jax.profiler.TraceAnnotation("step"):
                params, opt_state, comp_state, loss, metrics = fn(
                    params, opt_state, comp_state, jnp.int32(i),
                    jax.random.fold_in(key, i), batch,
                )
            with jax.profiler.TraceAnnotation("loss_read"):
                losses.append(float(loss))
            now = time.perf_counter()
            dt, last_read = now - last_read, now
            if i % log_every == 0 or i == steps - 1:
                # an MoE model's held experts: rows routed to the busiest
                # and to the average one, over layers
                rows = (f"expert_rows max {float(metrics[2]):.0f} "
                        f"mean {float(metrics[3]):.1f} "
                        if len(metrics) > 2 else "")
                print(
                    f"[train] step {i:5d} loss {losses[-1]:.4f} "
                    f"max_int {float(metrics[0]):.0f} bits {float(metrics[1]):.0f} "
                    f"{rows}dt {dt * 1e3:.1f}ms"
                )
            if ckpt and (i + 1) % ckpt_every == 0:
                with jax.profiler.TraceAnnotation("checkpoint"):
                    ckpt.save(i + 1, {"params": params, "opt": opt_state,
                                      "comp": comp_state})
            if tracing and i == profiled[-1]:
                jax.profiler.stop_trace()
                tracing = False
    finally:
        if tracing:
            jax.profiler.stop_trace()
    if ckpt:
        ckpt.wait()
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--compressor", default="intsgd")
    ap.add_argument("--opt", default="sgd", choices=["sgd", "adamw"],
                    help="base optimizer; both ride the fused Pallas "
                         "decode+update route under --fused")
    ap.add_argument("--wire", default=None,
                    help="wire codec for the integer gradient transport: "
                         + ", ".join(wire_format_names()))
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--fused", action="store_true",
                    help="route the update through the Pallas fused "
                         "dequantize+SGD kernel")
    ap.add_argument("--clip-norm", type=float, default=1.0)
    ap.add_argument("--overlap", default="off", choices=["off", "ring"],
                    help="wire transport: 'off' = one monolithic integer "
                         "psum; 'ring' = bucketed ppermute ring all-reduce "
                         "XLA overlaps with backward compute (bit-identical "
                         "result)")
    ap.add_argument("--bucket-words", type=int, default=DEFAULT_BUCKET_WORDS,
                    help="transport words per overlap bucket")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="grad-accum microbatches; with --overlap ring, "
                         "microbatch i's wire reduce runs behind microbatch "
                         "i+1's backward")
    ap.add_argument("--profile-dir", default=None,
                    help="record steps start+5 .. start+9 with the JAX "
                         "profiler into this directory")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = mesh_from_counts(data=args.data, model=args.model)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    ckpt = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
    train_loop(
        cfg, mesh, shape,
        compressor=args.compressor, steps=args.steps, lr=args.lr,
        ckpt=ckpt, resume=args.resume, fused=args.fused,
        clip_norm=args.clip_norm, wire=args.wire,
        overlap=args.overlap, bucket_words=args.bucket_words,
        microbatches=args.microbatches, opt=args.opt,
        profile_dir=args.profile_dir,
    )


if __name__ == "__main__":
    main()
