"""Tables 2/3 "Communication" column analogue, measured structurally: the
per-device collective bytes each algorithm's train step puts on the wire,
from the jaxpr cost walker on a (2 data × 2 model) debug mesh.

This is the CPU-only stand-in for the paper's wall-clock comparison: on
fixed hardware, all-reduce-able int8 beats all-reduce f32 beats all-gather —
the BYTES ordering here is exactly the paper's TIME ordering.

Two tables:
  * per-CODEC rows (the wire subsystem): f32 baseline vs DenseInt lanes vs
    PackedInt transport words, unfused and fused routes — the table that
    proves the bit-packed wire actually shrinks the data-parallel collective
    (dp_int column), not just the dtype bookkeeping;
  * per-COMPRESSOR rows (the paper's baselines) for continuity.

Artifacts: emits CSV rows (name,us_per_call,derived — us_per_call carries
dp_bytes, derived the breakdown) AND writes ``BENCH_comm_volume.json`` at
the repo root. ``--check`` asserts the codec compression ratios so CI can
smoke the table (see .github/workflows/ci.yml):

    dp_int(packed8)      <= dp_int(dense32) / 2   (is 4x: 1 vs 4 B/coord)
    dp_int(packed4)      <= dp_int(dense8)  / 2   (2x: sub-lane packing)
    dp(packed8_fused)    <= dp(dense32)     / 2   (the int8-packed recipe
        end to end vs the default transport; is 5x. Vs the int8 lane +
        ZeRO-1 route it is 2x-epsilon — the epsilon being 16 bytes of
        scalar metric psums — reported but not asserted.)
    dp_int(topk8_64)     <= dp_int(packed8) / 4   (the sparse gather
        payload: 64 idx+vals pairs per leaf vs a dense word per 4 coords —
        convergence at matched final loss is bench_convergence's logreg
        section)

Runs itself in a subprocess with 4 forced host devices so the parent
process' single-device view is untouched.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json
sys.path.insert(0, os.path.join(os.path.dirname(r"%(repo)s"), "%(repo_tail)s", "src"))
sys.path.insert(0, r"%(repo)s/src")
sys.path.insert(0, r"%(repo)s")
import jax, jax.numpy as jnp
from repro.configs import get_arch, smoke_config, ShapeConfig
from repro.core import make_compressor
from repro.launch.step import build_train_step
from repro.optim import sgd
from repro.optim.schedules import constant
from benchmarks.jaxpr_cost import analyze, summarize

mesh = jax.make_mesh((2, 2), ("data", "model"))
shape = ShapeConfig("t", 64, 8, "train")
cfg = smoke_config(get_arch("granite-8b"))

def measure(comp, fused=False):
    art = build_train_step(cfg, mesh, shape, compressor=comp,
                           base_opt=sgd(momentum=0.9), lr_schedule=constant(0.1),
                           fused=fused)
    s = summarize(analyze(art.jitted["compressed"], *art.arg_structs))
    return {"dp": s["dp_bytes"], "tp": s["tp_bytes"],
            "total": s["collective_bytes"], "dp_int": s["dp_int_bytes"]}

codecs = {
    "f32": ("none", None, False),
    "dense32": ("intsgd", None, False),
    "dense8": ("intsgd8", None, False),
    "dense4": ("intsgd4", None, False),
    "packed8": ("intsgd8", "packed8", False),
    "packed4": ("intsgd4", "packed4", False),
    "dense8_fused": ("intsgd8", None, True),
    "packed8_fused": ("intsgd8", "packed8", True),
    "topk8_64": ("intsgd8", "topk8:64", False),
}
out = {"codecs": {}, "compressors": {}}
for row, (name, wire, fused) in codecs.items():
    kw = {"wire": wire} if wire else {}
    out["codecs"][row] = measure(make_compressor(name, **kw), fused=fused)
for name in ["none", "allgather_sgd", "intsgd", "intsgd8", "heuristic_intsgd",
             "powersgd", "signsgd", "qsgd", "natsgd", "intdiana"]:
    out["compressors"][name] = measure(make_compressor(name))
print("RESULT " + json.dumps(out))
"""


def _ratios(codecs: dict) -> dict:
    div = lambda a, b: a / max(b, 1.0)
    return {
        "packed8_vs_dense32_dp_int": div(
            codecs["dense32"]["dp_int"], codecs["packed8"]["dp_int"]
        ),
        "packed4_vs_dense8_dp_int": div(
            codecs["dense8"]["dp_int"], codecs["packed4"]["dp_int"]
        ),
        "packed8_fused_vs_dense32_dp": div(
            codecs["dense32"]["dp"], codecs["packed8_fused"]["dp"]
        ),
        "packed8_fused_vs_dense8_dp": div(
            codecs["dense8"]["dp"], codecs["packed8_fused"]["dp"]
        ),
        "dense8_vs_f32_dp_int": div(
            codecs["f32"]["dp"], codecs["dense8"]["dp_int"]
        ),
        "topk8_64_vs_packed8_dp_int": div(
            codecs["packed8"]["dp_int"], codecs["topk8_64"]["dp_int"]
        ),
    }


def main(emit=print, check: bool = False):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # the child counts on forced host devices: keep it off any accelerator,
    # which another process may hold
    env["JAX_PLATFORMS"] = "cpu"
    code = _CHILD % {"repo": repo, "repo_tail": os.path.basename(repo)}
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=1800, env=env, cwd=repo,
    )
    if r.returncode != 0:
        emit(f"bench_comm_volume/ERROR,0,{r.stderr[-300:]!r}")
        if check:
            raise SystemExit(1)
        return
    out = None
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            out = json.loads(line[len("RESULT "):])
    if out is None:
        emit("bench_comm_volume/ERROR,0,'no RESULT line'")
        if check:
            raise SystemExit(1)
        return

    ratios = _ratios(out["codecs"])
    artifact = {
        "mesh": {"data": 2, "model": 2},
        "arch": "granite-8b (smoke)",
        "codecs": out["codecs"],
        "compressors": out["compressors"],
        "ratios": ratios,
    }
    with open(os.path.join(repo, "BENCH_comm_volume.json"), "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)

    for row, v in out["codecs"].items():
        emit(
            f"comm_volume/codec_{row},{v['dp']:.0f},total={v['total']:.0f}"
            f";dp_int={v['dp_int']:.0f}"
        )
    base = out["compressors"]["none"]["dp"]
    for name, v in out["compressors"].items():
        ratio = base / max(v["dp"], 1)
        emit(
            f"comm_volume/{name},{v['dp']:.0f},total={v['total']:.0f}"
            f";dp_int={v['dp_int']:.0f};dp_compression_vs_sgd={ratio:.2f}x"
        )
    for k, v in ratios.items():
        emit(f"comm_volume/ratio_{k},{v:.2f},")

    if check:
        failures = [
            k
            for k in (
                "packed8_vs_dense32_dp_int",
                "packed4_vs_dense8_dp_int",
                "packed8_fused_vs_dense32_dp",
            )
            if ratios[k] < 2.0
        ]
        # the sparse-wire headline (ROADMAP open item 1): the top-64 gather
        # payload beats packed8's dense words by >= 4x on the dp wire
        if ratios["topk8_64_vs_packed8_dp_int"] < 4.0:
            failures.append("topk8_64_vs_packed8_dp_int")
        if failures:
            emit(f"comm_volume/CHECK_FAILED,0,{failures!r}")
            raise SystemExit(1)
        emit("comm_volume/CHECK_OK,1,all codec ratios hold "
             "(packed >= 2x, topk >= 4x)")


if __name__ == "__main__":
    main(check="--check" in sys.argv[1:])
