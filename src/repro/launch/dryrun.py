import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede every other import (jax locks device count on first init).
# Only the dry-run sees 512 placeholder devices, on the CPU backend (never an
# accelerator another process may hold); tests/benches see 1.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this prints/records:
  * memory_analysis()  — per-device bytes (proves the sharding fits),
  * cost_analysis()    — per-device HLO FLOPs / bytes for §Roofline,
  * collective bytes   — parsed from compiled HLO (all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute result sizes),
  * the three roofline terms vs TPU v5e peaks.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] --out results.jsonl
Each cell can run in its own process (the sweep driver does this) so one
compile's heap doesn't bloat the next.
"""
import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

# TPU v5e hardware constants (targets; this container is CPU-only)
PEAK_FLOPS = 197e12  # bf16 FLOP/s per chip
HBM_BW = 819e9  # B/s per chip
ICI_BW = 50e9  # B/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\]))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes by collective kind, from the compiled SPMD module."""
    out = {}
    for shape_s, kind in _COLL_RE.findall(hlo_text):
        out[kind] = out.get(kind, 0) + _shape_bytes(shape_s)
    return out


def model_flops_per_chip(cfg, shape, n_chips: int) -> float:
    """6·N·D (train) / 2·N·B (decode, per emitted token), active params."""
    from repro.launch.specs import param_shapes
    import numpy as np

    g = param_shapes(cfg, 16, 1)
    n_total = int(sum(np.prod(x.shape) for x in jax.tree.leaves(g)))
    n_active = n_total
    if cfg.n_experts:  # subtract inactive expert params
        leaves = jax.tree_util.tree_flatten_with_path(g)[0]
        expert_params = sum(
            int(np.prod(l.shape))
            for p, l in leaves
            if any(getattr(q, "key", "") in ("w_gate", "w_up", "w_down") for q in p)
            and l.ndim == 4  # stacked (L, E, ...)
        )
        n_active = n_total - expert_params + expert_params * (
            (cfg.top_k + cfg.n_shared_experts) / max(cfg.n_experts, 1)
        )
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active * tokens / n_chips
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens / n_chips
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch / n_chips


def run_cell(arch: str, shape_name: str, multi_pod: bool, compressor: str = "intsgd",
             tp_override=None, remat_policy="full"):
    from repro.configs import get_arch, get_shape
    from repro.core import make_compressor
    from repro.launch.mesh import make_production_mesh
    from repro.launch.step import build_serve_step, build_train_step
    from repro.optim import sgd
    from repro.optim.schedules import constant

    import dataclasses as _dc

    cfg = get_arch(arch)
    if remat_policy != "full":
        cfg = _dc.replace(cfg, remat_policy=remat_policy)
    shape = get_shape(shape_name)
    if shape_name == "long_500k" and not cfg.subquadratic:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "skipped": "full-attention arch (see DESIGN.md §shape-skips)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    if shape.kind == "train":
        art = build_train_step(
            cfg, mesh, shape,
            compressor=make_compressor(compressor),
            base_opt=sgd(momentum=0.9, weight_decay=1e-4),
            lr_schedule=constant(0.1),
            tp_override=tp_override,
        )
        fn = art.jitted["compressed"]
    else:
        art = build_serve_step(cfg, mesh, shape)
        fn = art.jitted["prefill" if shape.kind == "prefill" else "decode"]

    lowered = fn.lower(*art.arg_structs)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # older JAX: one dict per device
        ca = ca[0] if ca else {}
    hlo_flops = float(ca.get("flops", 0.0))
    hlo_bytes = float(ca.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())

    # structural (jaxpr-level) cost: multiplies through scan trip counts —
    # the numbers the roofline uses (HLO cost_analysis counts while-loop
    # bodies ONCE, undercounting scanned layers by ~L×; both recorded).
    import sys as _sys, os as _os
    _sys.path.insert(0, _os.path.join(_os.path.dirname(__file__), "..", "..", ".."))
    from benchmarks.jaxpr_cost import analyze, summarize

    t2 = time.time()
    struct = summarize(analyze(fn, *art.arg_structs))
    t_struct = time.time() - t2

    mf = model_flops_per_chip(cfg, shape, n_chips)
    terms = {
        "compute_s": struct["flops"] / PEAK_FLOPS,
        # post-fusion HBM estimate; struct["bytes"]/HBM_BW is the unfused
        # upper bound, also recorded
        "memory_s": struct["bytes_fused"] / HBM_BW,
        "memory_unfused_s": struct["bytes"] / HBM_BW,
        "collective_s": struct["collective_bytes"] / ICI_BW,
    }
    core_terms = {k: terms[k] for k in ("compute_s", "memory_s", "collective_s")}
    dominant = max(core_terms, key=core_terms.get)
    rec = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "n_chips": n_chips,
        "compressor": compressor if shape.kind == "train" else None,
        "tp_override": tp_override,
        "remat_policy": remat_policy,
        "struct": struct,
        "hlo_flops_per_chip": hlo_flops,
        "hlo_bytes_per_chip": hlo_bytes,
        "hlo_collectives": coll,
        "memory": mem,
        "roofline": terms,
        "dominant": dominant,
        "model_flops_per_chip": mf,
        "useful_flops_frac": mf / struct["flops"] if struct["flops"] else None,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "struct_s": round(t_struct, 1),
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--compressor", default="intsgd")
    ap.add_argument("--tp", type=int, default=None)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.configs import runnable_cells

    if args.all:
        cells = [(a, s) for a, s, r in runnable_cells() if r]
    else:
        cells = [(args.arch, args.shape)]

    for arch, shape in cells:
        try:
            rec = run_cell(arch, shape, args.multi_pod, args.compressor,
                           tp_override=args.tp, remat_policy=args.remat)
        except Exception as e:  # record failures, they are bugs to fix
            rec = {"arch": arch, "shape": shape, "multi_pod": args.multi_pod,
                   "error": f"{type(e).__name__}: {e}"}
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
