#!/usr/bin/env python3
"""Run the IntSGD train step on the TPU and check what it computes.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the data-parallel integer wire, 4 chips

Both drive granite-8b at its published widths (d_model 4096, 32 heads of
128, 8 KV heads, d_ff 14336, vocab 49152), cut in depth only, through the
builders the trainer uses (``init_lm_params``, ``build_init_state``,
``build_train_step``), on synthetic data and weights made from a seed.

One chip, four phases:

  a  the float step (``exact``: the float gradient all-reduce the trainer
     runs at step 0), for every step;
  b  IntSGD, ``intsgd8`` on the ``packed8`` wire, default ZeRO-1 route:
     step 0 is the float step, as in the trainer, then the compressed step;
  c  the same on the fused Pallas decode+update route (the compiled step
     must hold the kernel: ``tpu_custom_call``);
  d  the Pallas encode and pack kernels on one real-width leaf, bit for bit
     against ``kernels/ref.py``.

Four chips (``--chips 4``) runs only this: the same cut on a data=4 mesh,
IntSGD ``packed8`` on the ZeRO-1 route against its own float step. It checks
that the parameters are replicated on all four devices and the ZeRO-1 state
is split across them, that the compiled IntSGD step reduces gradients with
integer collectives only, and the losses.

Measurements go on earlier lines, each with the device it ran on. The last
line is one JSON object, ``{"ok": true, "device": {...}}``. A failed check
raises, so the script exits non-zero without that line. It refuses to run
anywhere but a TPU; the compile cache goes where
``repro.launch.compile_cache`` says.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import ShapeConfig, get_arch  # noqa: E402
from repro.core import make_compressor  # noqa: E402
from repro.data.synthetic import SyntheticLMData  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.prng import seed_from_key  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.step import build_init_state, build_train_step  # noqa: E402
from repro.models.transformer import init_lm_params  # noqa: E402
from repro.optim import sgd  # noqa: E402
from repro.optim.schedules import constant, warmup_wrap  # noqa: E402
from repro.parallel.collectives import mesh_from_counts  # noqa: E402
from repro.wire import PackedInt  # noqa: E402

ARCH = "granite-8b"
# The cut, from compiled.memory_analysis() of the compressed ZeRO-1 step for
# a described v5e chip (f32 parameters, as the trainer passes them): one
# layer at seq 2048 x batch 2 needs 12.8 GB of the chip's 16 GiB (7.4 GB of
# parameters, ZeRO-1 master and momentum, 5.3 GB of temporaries). A second
# layer adds 218 M parameters, about 4 GB more in these buffers: over.
LAYERS = 1
SEQ = 2048
BATCH_PER_CHIP = 2
STEPS = 8
LR = 0.3  # the trainer's default, behind its 5-step warmup
SEED = 0
COMPRESSOR, WIRE = "intsgd8", "packed8"

# Step 0 is the same float step on the same parameters and batch on every
# route; two separately compiled programs may order the f32 reductions of
# the loss differently, and nothing else.
STEP0_RTOL = 1e-5
# The ZeRO-1 and fused routes do the same arithmetic op for op, one in XLA
# and one in Pallas, and agree bit for bit on the CPU. On the TPU they are
# two programs compiled apart: should any reduction (the gradient-clip norm)
# come out in another order, the ULP difference turns a few stochastic-
# rounding draws into whole quantization steps, and the losses drift apart
# (a one-ULP difference in the decode did 1.7e-4 in 6 steps at smoke width
# on the CPU). A wrong decode or update moves the loss by far more.
ROUTE_RTOL = 2e-3
# IntSGD adds zero-mean rounding noise (variance <= 1/4 a coordinate of the
# integer image, Lemma 1) and clips at the 8-bit limit for 4 workers
# (|v| <= 31), which slows its first steps against the float run: 2.9e-2
# apart after 5 steps at smoke width on four CPU devices. The band is 10%;
# a wire that loses or corrupts gradients leaves it, or stops the loss from
# falling, which is checked too.
WIRE_RTOL = 1e-1
# Float reductions up to this many elements are scalars and statistics
# (loss, metrics, norms), not gradients: the wire auditor's W001 allowance.
SCALAR_ELEMS = 64


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_label() -> str:
    d = jax.devices()[0]
    return f"{d.platform}/{d.device_kind} x{len(jax.devices())}"


# ---------------------------------------------------------------------------
# the trainer's pieces
# ---------------------------------------------------------------------------
def build(cfg, mesh, shape, *, fused: bool):
    """The trainer's step and state builders with its defaults
    (launch/train.py::train_loop): momentum SGD, warmup, clip 1.0, f32."""
    comp = make_compressor(COMPRESSOR, wire=WIRE)
    opt = sgd(momentum=0.9, weight_decay=1e-4)
    art = build_train_step(
        cfg, mesh, shape, compressor=comp, base_opt=opt,
        lr_schedule=warmup_wrap(constant(LR), 5), param_dtype=jnp.float32,
        fused=fused, clip_norm=1.0,
    )
    init = build_init_state(cfg, mesh, compressor=comp, base_opt=opt,
                            fused=fused)
    return art, init


def compile_step(art, which: str, name: str, emit):
    t = time.perf_counter()
    compiled = art.jitted[which].lower(*art.arg_structs).compile()
    dt = time.perf_counter() - t
    ma = compiled.memory_analysis()
    emit(
        f"compile {name}/{which}: {dt:.1f} s; per device: arguments "
        f"{ma.argument_size_in_bytes} B, temporaries {ma.temp_size_in_bytes} B,"
        f" outputs {ma.output_size_in_bytes} B ({ma.alias_size_in_bytes} B "
        f"aliased to arguments)"
    )
    return compiled


def fresh_state(cfg, art, init):
    """Seeded parameters, placed as the step wants them, and their state."""
    params = jax.jit(
        lambda k: init_lm_params(k, cfg, tp=1, n_shards=1, dtype=jnp.float32),
        out_shardings=art.in_shardings[0],
    )(jax.random.PRNGKey(SEED))
    opt_state, comp_state = init(params)
    return params, opt_state, comp_state


def train(cfg, shape, art, state, programs, *, name: str, emit):
    """Run len(programs) steps, step i with compiled program programs[i], on
    the trainer's data. Returns (losses, final params' squared norm)."""
    params, opt_state, comp_state = state
    data = SyntheticLMData(cfg.vocab, shape.seq_len, shape.global_batch,
                           seed=SEED)
    key = jax.random.PRNGKey(SEED)
    sh = art.in_shardings
    losses = []
    for i, prog in enumerate(programs):
        batch = {k: jax.device_put(v, sh[5][k])
                 for k, v in data.batch(i, 0).items()}
        step = jax.device_put(jnp.int32(i), sh[3])
        skey = jax.device_put(jax.random.fold_in(key, i), sh[4])
        jax.block_until_ready((batch, step, skey))
        t = time.perf_counter()
        out = prog(params, opt_state, comp_state, step, skey, batch)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t
        params, opt_state, comp_state, loss, metrics = out
        losses.append(float(loss))
        emit(
            f"{name} step {i}: loss {losses[-1]:.6f} max_int "
            f"{float(metrics[0]):.0f} step_time {dt * 1e3:.3f} ms "
            f"({device_label()})"
        )
    sq = float(sum(jnp.sum(jnp.square(p)) for p in jax.tree.leaves(params)))
    return losses, sq


def check_losses(name: str, losses) -> None:
    check(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{name}: loss did not fall ({losses[0]} -> {losses[-1]})")


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


# ---------------------------------------------------------------------------
# collectives in compiled HLO
# ---------------------------------------------------------------------------
_OP = re.compile(r"=\s*(?P<type>.+?)\s(?P<op>[\w-]+)\(")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def collectives(hlo_text: str, kind: str):
    """[(opcode, dtype, elements)] of every `kind` collective
    ("all-reduce", "reduce-scatter", "all-gather") in compiled HLO text,
    async starts included, their -done halves not."""
    out = []
    for line in hlo_text.splitlines():
        m = _OP.search(line)
        if not m or kind not in m["op"] or m["op"].endswith("-done"):
            continue
        for dtype, dims in _ARRAY.findall(m["type"]):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            out.append((m["op"], dtype, n))
    return out


def gradient_reductions(hlo_text: str):
    """(float, integer) reductions with more than SCALAR_ELEMS elements."""
    reds = (collectives(hlo_text, "all-reduce")
            + collectives(hlo_text, "reduce-scatter"))
    big = [r for r in reds if r[2] > SCALAR_ELEMS]
    floats = [r for r in big if r[1].startswith(("f", "bf"))]
    ints = [r for r in big if r[1].startswith(("s", "u"))]
    return floats, ints


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def run_one_chip(cfg, shape, *, steps: int = STEPS, emit=print,
                 on_chip: bool = True):
    """Phases a-d on one device. ``on_chip=False`` is for rehearsing on the
    CPU, where the kernels run in interpret mode and so leave no kernel
    call in the compiled program to look for."""
    mesh = mesh_from_counts(data=1, model=1)

    art, init = build(cfg, mesh, shape, fused=False)
    zero1 = {w: compile_step(art, w, "zero1", emit)
             for w in ("exact", "compressed")}
    loss_a, _ = train(cfg, shape, art, fresh_state(cfg, art, init),
                      [zero1["exact"]] * steps, name="a float", emit=emit)
    loss_b, sq_b = train(
        cfg, shape, art, fresh_state(cfg, art, init),
        [zero1["exact"]] + [zero1["compressed"]] * (steps - 1),
        name="b intsgd zero1", emit=emit,
    )
    del zero1

    art_f, init_f = build(cfg, mesh, shape, fused=True)
    fused = {w: compile_step(art_f, w, "fused", emit)
             for w in ("exact", "compressed")}
    has_kernel = "tpu_custom_call" in fused["compressed"].as_text()
    emit(f"fused compressed step holds a Pallas kernel (tpu_custom_call): "
         f"{has_kernel}")
    if on_chip:
        check(has_kernel, "fused step has no tpu_custom_call")
    loss_c, sq_c = train(
        cfg, shape, art_f, fresh_state(cfg, art_f, init_f),
        [fused["exact"]] + [fused["compressed"]] * (steps - 1),
        name="c intsgd fused", emit=emit,
    )
    del fused

    for name, losses in (("a", loss_a), ("b", loss_b), ("c", loss_c)):
        check_losses(name, losses)
    emit(f"step-0 loss a {loss_a[0]!r} b {loss_b[0]!r} c {loss_c[0]!r}; "
         f"largest relative difference "
         f"{max(rel(loss_b[0], loss_a[0]), rel(loss_c[0], loss_a[0])):.3e} "
         f"(limit {STEP0_RTOL})")
    check(rel(loss_b[0], loss_a[0]) <= STEP0_RTOL
          and rel(loss_c[0], loss_a[0]) <= STEP0_RTOL,
          "step-0 losses disagree")
    route = max(rel(c, b) for b, c in zip(loss_b, loss_c))
    emit(f"fused vs zero1 route: largest relative loss difference "
         f"{route:.3e} (limit {ROUTE_RTOL}); final squared parameter norm "
         f"{sq_b!r} vs {sq_c!r}, relative {rel(sq_c, sq_b):.3e}")
    check(route <= ROUTE_RTOL, "fused and zero1 routes disagree")
    check(rel(sq_c, sq_b) <= ROUTE_RTOL, "fused and zero1 parameters differ")
    emit(f"intsgd zero1 vs float: final loss {loss_b[-1]!r} vs "
         f"{loss_a[-1]!r}, relative {rel(loss_b[-1], loss_a[-1]):.3e}")

    check_encode_kernel((cfg.d_model, cfg.d_ff), emit)


def check_encode_kernel(leaf_shape, emit):
    """Phase d: the ``use_kernels`` codec (Pallas encode + pack) on one
    leaf, against the oracle, bit for bit."""
    kx, kr = jax.random.split(jax.random.PRNGKey(SEED + 1))
    x = jax.random.normal(kx, leaf_shape, jnp.float32)
    alpha = jnp.float32(60.0)  # |αx| spans the 8-bit clip; a few % clip
    wf = PackedInt(bits=8, use_kernels=True)
    ints = wf.encode(x, alpha, kr, n_workers=1)
    want = ref.int_compress_ref(x, alpha, seed_from_key(kr), n_workers=1,
                                bits=8)
    words = wf.pack(ints, n_workers=1)
    want_words = ref.pack_words_ref(ints, bits=8, n_workers=1)
    same_ints = bool(jnp.array_equal(ints, want))
    same_words = bool(jnp.array_equal(words, want_words))
    clipped = float(jnp.mean((jnp.abs(ints) == 127).astype(jnp.float32)))
    emit(f"d encode kernel on a {leaf_shape} leaf ({device_label()}): "
         f"equal to ref {same_ints}, pack kernel equal to ref {same_words}, "
         f"clipped share {clipped:.4f}")
    check(same_ints, "encode kernel differs from kernels/ref.py")
    check(same_words, "pack kernel differs from kernels/ref.py")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def run_four_chips(cfg, shape, *, steps: int = STEPS, emit=print):
    """IntSGD packed8 on the ZeRO-1 route of a data=4 mesh against its own
    float step: placement, integer-only gradient reductions, losses."""
    mesh = mesh_from_counts(data=4, model=1)
    devices = set(mesh.devices.flat)
    art, init = build(cfg, mesh, shape, fused=False)
    prog = {w: compile_step(art, w, "zero1 dp4", emit)
            for w in ("exact", "compressed")}

    f_int, i_int = gradient_reductions(prog["compressed"].as_text())
    f_exact, _ = gradient_reductions(prog["exact"].as_text())
    gathers = collectives(prog["compressed"].as_text(), "all-gather")
    emit(f"compiled intsgd step: float gradient reductions {f_int}; integer "
         f"reductions {i_int}; all-gathers (ZeRO-1 parameters, float by "
         f"design) {len(gathers)}, dtypes {sorted({g[1] for g in gathers})}")
    emit(f"compiled float step, for contrast: float gradient reductions "
         f"{f_exact}")
    check(not f_int, "IntSGD step reduces a float tensor of gradient size")
    check(bool(i_int), "IntSGD step has no integer gradient reduction")
    check(bool(f_exact), "float step shows no float gradient reduction")

    state = fresh_state(cfg, art, init)
    params, opt_state, _ = state
    rep = all(p.sharding.is_fully_replicated and p.sharding.device_set ==
              devices for p in jax.tree.leaves(params))
    masters = [x for x in jax.tree.leaves(opt_state) if x.ndim >= 2]
    split = all(
        not m.sharding.is_fully_replicated
        and {s.device for s in m.addressable_shards} == devices
        and all(s.data.shape[0] * len(devices) == m.shape[0]
                for s in m.addressable_shards)
        for m in masters
    )
    emit(f"parameters replicated on all {len(devices)} devices: {rep}; "
         f"ZeRO-1 master and momentum split over them: {split} (first "
         f"master {masters[0].shape}, shards "
         f"{[tuple(s.data.shape) for s in masters[0].addressable_shards]})")
    check(rep, "parameters are not replicated on every device")
    check(split, "ZeRO-1 state is not split across the devices")
    del params, opt_state

    loss_f, _ = train(cfg, shape, art, state, [prog["exact"]] * steps,
                      name="dp4 float", emit=emit)
    del state
    loss_i, _ = train(cfg, shape, art, fresh_state(cfg, art, init),
                      [prog["exact"]] + [prog["compressed"]] * (steps - 1),
                      name="dp4 intsgd", emit=emit)
    check_losses("dp4 float", loss_f)
    check_losses("dp4 intsgd", loss_i)
    worst = max(rel(i, f) for i, f in zip(loss_i, loss_f))
    emit(f"dp4 step-0 loss float {loss_f[0]!r} intsgd {loss_i[0]!r}; intsgd "
         f"vs float largest relative difference {worst:.3e} (limit "
         f"{WIRE_RTOL})")
    check(rel(loss_i[0], loss_f[0]) <= STEP0_RTOL, "dp4 step-0 losses differ")
    check(worst <= WIRE_RTOL, "IntSGD losses left the float run's band")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel integer wire phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, "
              f"{len(jax.devices())} found", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    full = get_arch(ARCH)
    cfg = dataclasses.replace(full, n_layers=LAYERS)
    shape = ShapeConfig("chip_smoke", SEQ, BATCH_PER_CHIP * args.chips,
                        "train")
    emit = lambda s: print(s, flush=True)
    emit(f"{ARCH} at published widths: d_model {cfg.d_model}, {cfg.n_heads} "
         f"heads of {cfg.head_dim}, {cfg.n_kv_heads} KV heads, d_ff "
         f"{cfg.d_ff}, vocab {cfg.vocab}; cut: {LAYERS} of {full.n_layers} "
         f"layers, seq {SEQ}, batch {BATCH_PER_CHIP} per chip x "
         f"{args.chips}; {COMPRESSOR} on {WIRE}; {device_label()}")
    if args.chips == 4:
        run_four_chips(cfg, shape, emit=emit)
    else:
        run_one_chip(cfg, shape, emit=emit)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        emit(f"peak device memory {stats['peak_bytes_in_use']} B "
             f"({device_label()})")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
