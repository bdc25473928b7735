"""Training driver: end-to-end loop with checkpointing, fault tolerance and
elastic re-mesh.

CLI (CPU-scale demo; the same builder lowers for the production mesh in
dryrun.py):

  PYTHONPATH=src python -m repro.launch.train \\
      --arch granite-8b --smoke --steps 50 --compressor intsgd \\
      --ckpt-dir /tmp/ckpt [--resume] [--data 2 --model 2]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointStore
from repro.configs import ShapeConfig, get_arch, smoke_config
from repro.core import make_compressor, with_wire
from repro.data.synthetic import SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.step import build_init_state, build_train_step
from repro.models.transformer import init_lm_params
from repro.optim import adamw, sgd
from repro.optim.schedules import constant, warmup_wrap
from repro.parallel.collectives import mesh_from_counts
from repro.wire import wire_format_names
from repro.wire.bucketing import DEFAULT_BUCKET_WORDS


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

    data = SyntheticLMData(
        cfg.vocab, shape.seq_len, shape.global_batch, seed=seed
    )
    batch_sharding = art.in_shardings[5]

    losses = []
    for i in range(start, steps):
        batch = data.batch(i, 0)  # global batch; sharded by device_put
        batch = {k: jax.device_put(v, batch_sharding[k]) for k, v in batch.items()}
        fn = art.jitted["exact"] if i == 0 else art.jitted["compressed"]
        t0 = time.time()
        params, opt_state, comp_state, loss, metrics = fn(
            params, opt_state, comp_state, jnp.int32(i), jax.random.fold_in(key, i), batch
        )
        if i % log_every == 0 or i == steps - 1:
            print(
                f"[train] step {i:5d} loss {float(loss):.4f} "
                f"max_int {float(metrics[0]):.0f} bits {float(metrics[1]):.0f} "
                f"dt {time.time()-t0:.2f}s"
            )
        losses.append(float(loss))
        if ckpt and (i + 1) % ckpt_every == 0:
            ckpt.save(i + 1, {"params": params, "opt": opt_state, "comp": comp_state})
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
    )


if __name__ == "__main__":
    main()
