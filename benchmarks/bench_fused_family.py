"""The fused decode+update family, measured structurally and on the clock.

PR 4 extended the Pallas fused route from (IntSGD × momentum-SGD) to the
full capability matrix — {sgd, adamw} optimizer kernels × {dense, packed}
codecs × {IntSGD, IntDIANA} compressors. This bench builds the fused train
step for each (optimizer × codec) pair and reports, from the jaxpr of the
built step (benchmarks.jaxpr_cost):

  * ``n_pallas_calls`` — fused kernel launches per step (one per param
    leaf: decode + moment update + apply in a single HBM pass each);
  * ``image_hbm_roundtrips`` — int32 inputs of INTEGER-IMAGE size entering
    a Pallas kernel. On the packed codec the kernels must consume the d/k
    transport words directly (in-register unpack), so this is 0: the
    summed integer image never makes an HBM round-trip between the
    all-reduce and the parameters. A nonzero count means someone unpacked
    outside the kernel;
  * ``bytes_fused`` / ``dp_int_bytes`` / ``flops`` — the jaxpr_cost
    structural totals (post-fusion HBM-byte estimate, integer dp collective
    bytes, FLOPs);
  * ``cpu_interpret_step_ms`` — wall-clock per compressed step on the
    forced CPU host devices, kernels in Pallas interpret mode. It is not a
    device time and says nothing about the TPU.

``--check`` asserts the headline HBM-pass property: the fused AdamW route
performs NO MORE integer-image HBM round-trips than fused SGD — i.e. zero
on the packed codec — and launches the same number of fused kernels (the
extra moment tensor rides the same pass, not an extra one). Wired into CI
next to the bench_comm_volume / bench_overlap smokes. Artifact:
``BENCH_fused_family.json`` at the repo root.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json, time
sys.path.insert(0, r"%(repo)s/src")
sys.path.insert(0, r"%(repo)s")
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_arch, smoke_config, ShapeConfig
from repro.core import make_compressor
from repro.launch.inputs import materialize_batch
from repro.launch.step import build_train_step, build_init_state
from repro.models.transformer import init_lm_params
from repro.optim import adamw, sgd
from repro.optim.schedules import constant
from benchmarks.jaxpr_cost import analyze, summarize, iter_eqns

mesh = jax.make_mesh((2, 2), ("data", "model"))
shape = ShapeConfig("t", 64, 8, "train")
cfg = smoke_config(get_arch("granite-8b"))
key = jax.random.PRNGKey(0)

def pallas_stats(jaxpr):
    calls = 0
    image_roundtrips = 0
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        calls += 1
        f32_out = [v.aval for v in eqn.outvars
                   if str(v.aval.dtype) == "float32"]
        if not f32_out:
            continue
        image = max(int(np.prod(a.shape)) for a in f32_out)
        for v in eqn.invars:
            a = getattr(v, "aval", None)
            if a is None or not hasattr(a, "shape"):
                continue
            # image-sized int32 into the kernel = the decoded integer image
            # took an HBM round-trip; packed transport words are image/k
            if str(a.dtype) == "int32" and int(np.prod(a.shape)) > image // 2:
                image_roundtrips += 1
    return calls, image_roundtrips

def measure(opt_name, comp_name, wire_name):
    comp = make_compressor(comp_name, bits=8)
    opt = {"sgd": sgd(momentum=0.9, weight_decay=1e-4),
           "adamw": adamw()}[opt_name]
    art = build_train_step(
        cfg, mesh, shape, compressor=comp, base_opt=opt,
        lr_schedule=constant(0.01), param_dtype=jnp.float32,
        fused=True, donate=False, wire=wire_name,
    )
    fn = art.jitted["compressed"]
    closed = jax.make_jaxpr(fn)(*art.arg_structs)
    calls, rt = pallas_stats(closed.jaxpr)
    s = summarize(analyze(fn, *art.arg_structs))
    params = init_lm_params(key, cfg, tp=2, n_shards=1, dtype=jnp.float32)
    params = jax.device_put(params, art.in_shardings[0])
    init = build_init_state(cfg, mesh, compressor=comp, base_opt=opt,
                            fused=True)
    opt_state, comp_state = init(params)
    batch = materialize_batch(cfg, shape, key)
    args = lambda i: (params, opt_state, comp_state, jnp.int32(i),
                      jax.random.fold_in(key, i), batch)
    jax.block_until_ready(fn(*args(0)))  # compile + warm
    t0 = time.time()
    reps = 2
    for i in range(1, 1 + reps):
        out = fn(*args(i))
    jax.block_until_ready(out)
    return {
        "n_pallas_calls": calls,
        "image_hbm_roundtrips": rt,
        "bytes_fused": s["bytes_fused"],
        "dp_int_bytes": s["dp_int_bytes"],
        "flops": s["flops"],
        "cpu_interpret_step_ms": (time.time() - t0) / reps * 1e3,
    }

rows = {}
for opt_name in ("sgd", "adamw"):
    for wire_name in ("dense8", "packed8"):
        rows[f"{opt_name}+intsgd8+{wire_name}"] = measure(
            opt_name, "intsgd", wire_name)
rows["adamw+intdiana+packed8"] = measure("adamw", "intdiana", "packed8")
print("RESULT " + json.dumps(rows))
"""


def main(emit=print, check: bool = False):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # the child counts on forced host devices: keep it off any accelerator,
    # which another process may hold
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _CHILD % {"repo": repo}],
        capture_output=True, text=True, timeout=1800, env=env, cwd=repo,
    )
    if r.returncode != 0:
        emit(f"bench_fused_family/ERROR,0,{r.stderr[-300:]!r}")
        if check:
            raise SystemExit(1)
        return
    rows = None
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            rows = json.loads(line[len("RESULT "):])
    if rows is None:
        emit("bench_fused_family/ERROR,0,'no RESULT line'")
        if check:
            raise SystemExit(1)
        return

    artifact = {
        "mesh": {"data": 2, "model": 2},
        "arch": "granite-8b (smoke)",
        "rows": rows,
    }
    with open(os.path.join(repo, "BENCH_fused_family.json"), "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)

    for name, row in rows.items():
        emit(
            f"fused_family/{name},{row['cpu_interpret_step_ms']:.1f},"
            f"pallas={row['n_pallas_calls']}"
            f";image_rt={row['image_hbm_roundtrips']}"
            f";bytes_fused={row['bytes_fused']:.3e}"
            f";dp_int_bytes={row['dp_int_bytes']:.0f}"
        )

    if check:
        failures = []
        sgd_row = rows["sgd+intsgd8+packed8"]
        adamw_row = rows["adamw+intsgd8+packed8"]
        if adamw_row["image_hbm_roundtrips"] > sgd_row["image_hbm_roundtrips"]:
            failures.append(
                "fused AdamW makes more integer-image HBM round-trips than "
                f"fused SGD: {adamw_row['image_hbm_roundtrips']} > "
                f"{sgd_row['image_hbm_roundtrips']}"
            )
        for name in ("sgd+intsgd8+packed8", "adamw+intsgd8+packed8",
                     "adamw+intdiana+packed8"):
            if rows[name]["image_hbm_roundtrips"] != 0:
                failures.append(
                    f"{name}: packed fused route let the integer image "
                    f"round-trip HBM {rows[name]['image_hbm_roundtrips']}×; "
                    "the kernels must consume transport words in-register"
                )
        if adamw_row["n_pallas_calls"] != sgd_row["n_pallas_calls"]:
            failures.append(
                "fused AdamW launches a different kernel count than fused "
                f"SGD ({adamw_row['n_pallas_calls']} vs "
                f"{sgd_row['n_pallas_calls']}): the extra moment tensor "
                "must ride the same pass, not an extra launch"
            )
        if failures:
            emit(f"fused_family/CHECK_FAILED,0,{failures!r}")
            raise SystemExit(1)
        emit(
            "fused_family/CHECK_OK,1,adamw fused route: zero integer-image "
            "HBM round-trips, same kernel-launch count as sgd"
        )


if __name__ == "__main__":
    main(check="--check" in sys.argv[1:])
