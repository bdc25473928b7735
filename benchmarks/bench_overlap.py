"""Overlap-readiness of the wire transport, measured structurally.

The serial route puts ONE monolithic integer psum on the critical path —
nothing for XLA's latency-hiding scheduler to work with. The bucketed ring
route (``overlap="ring"``) cuts the same transport words into fixed-size
buckets and reduces each with an independent ppermute ring + chunk
all-gather: many small collectives whose hops can interleave with whatever
compute is still pending (the next microbatch's backward, the unpack of the
previous bucket). This bench counts exactly that, from the jaxpr of the
built train step:

  * the serial route emits exactly 1 integer dp collective;
  * the bucketed route emits >= 2 (one ring per bucket — the interleavable
    units);
  * the per-worker wire PAYLOAD is unchanged: the bucket manifest's bytes
    equal the serial route's integer dp psum bytes, and both equal the
    ``packed8`` dp_int row of BENCH_comm_volume.json (bucketing is slicing
    bookkeeping, not re-encoding — zero byte inflation).

Since PR 9 the runtime counts are no longer the only evidence: each route
also carries a STATIC column derived by :mod:`repro.analysis.schedule` /
``traffic`` from the spec alone — declared collective count/bytes
(``BucketManifest.ring_collectives`` must agree) and the static roofline
fractions (``hidden``/``interleavable``). ``--check`` asserts
static == measured per route, and pins the fresh static counts against the
COMMITTED ``BENCH_overlap.json`` (12 bucketed vs 1 serial on this debug
mesh), so a transport change must regenerate the artifact explicitly.

Artifact: ``BENCH_overlap.json`` at the repo root, the PR 2 JSON pattern.
Runs in a subprocess with 4 forced host devices on the same (2 data x 2
model) debug mesh as bench_comm_volume, so the byte comparison is
apples-to-apples.
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
sys.path.insert(0, r"%(repo)s/src")
sys.path.insert(0, r"%(repo)s")
import jax, jax.numpy as jnp
from repro.configs import get_arch, smoke_config, ShapeConfig
from repro.core import make_compressor
from repro.launch.step import build_train_step, resolve_layout
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.wire import PackedInt, plan_buckets
from repro.analysis import schedule as schedule_mod
from benchmarks.jaxpr_cost import analyze, summarize, _axes_of, iter_eqns

BUCKET_WORDS = 4096
mesh = jax.make_mesh((2, 2), ("data", "model"))
shape = ShapeConfig("t", 64, 8, "train")
cfg = smoke_config(get_arch("granite-8b"))

def count_int_dp_collectives(jaxpr):
    # interleavable integer collectives on the data-parallel axes: the
    # units XLA's scheduler can overlap with pending compute
    out = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in ("psum", "ppermute", "all_gather"):
            continue
        axes = _axes_of(eqn)
        if axes == ("model",):
            continue
        if not any(
            hasattr(v, "aval")
            and jnp.issubdtype(jnp.dtype(v.aval.dtype), jnp.integer)
            for v in eqn.invars
        ):
            continue
        out[name] = out.get(name, 0) + 1
    return out

def measure(overlap):
    comp = make_compressor("intsgd8", wire=PackedInt(bits=8))
    art = build_train_step(
        cfg, mesh, shape, compressor=comp, base_opt=sgd(momentum=0.9),
        lr_schedule=constant(0.1), overlap=overlap,
        bucket_words=BUCKET_WORDS,
    )
    fn = art.jitted["compressed"]
    closed = jax.make_jaxpr(fn)(*art.arg_structs)
    counts = count_int_dp_collectives(closed.jaxpr)
    s = summarize(analyze(fn, *art.arg_structs))
    # the static column: same trace, but counts/bytes DERIVED from the
    # declared transport model + the dependence-graph roofline (PR 9)
    rep = schedule_mod.full_audit(closed, art.audit_spec)
    plan = rep.traffic.plan
    return {
        "collective_eqns": counts,
        "n_int_dp_collectives": sum(counts.values()),
        "dp_int_bytes": s["dp_int_bytes"],
        "dp_bytes": s["dp_bytes"],
        "static": {
            "declared_eqns": plan.n_eqns,
            "declared_bytes": plan.coll_bytes,
            "observed_eqns": rep.traffic.observed_eqns,
            "n_serialized": rep.schedule.n_serialized,
            "hidden_fraction": round(rep.schedule.hidden_fraction, 6),
            "interleavable_fraction": round(
                rep.schedule.interleavable_fraction, 6
            ),
            "ok": rep.ok,
            "rules": sorted({v.rule for v in rep.violations}),
        },
    }

serial = measure("off")
bucketed = measure("ring")

# the bucket manifest: payload bytes of the SAME words tree, bucketed
layout = resolve_layout(cfg, mesh)
wf = PackedInt(bits=8)
n = layout.n_dp
words_struct = jax.eval_shape(
    lambda t: jax.tree.map(lambda v: wf.pack(v, n_workers=n), t),
    layout.l_shapes,
)
manifest = plan_buckets(words_struct, bucket_words=BUCKET_WORDS)
bucketed["n_buckets"] = manifest.n_buckets
bucketed["manifest_bytes"] = manifest.payload_bytes
bucketed["bucket_words"] = BUCKET_WORDS
ring_eqns, ring_bytes = manifest.ring_collectives(
    tuple(mesh.shape[a] for a in ("data",))
)
bucketed["manifest_ring_eqns"] = ring_eqns
bucketed["manifest_ring_bytes"] = ring_bytes
print("RESULT " + json.dumps({"serial": serial, "bucketed": bucketed}))
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
        emit(f"bench_overlap/ERROR,0,{r.stderr[-300:]!r}")
        if check:
            raise SystemExit(1)
        return
    out = None
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            out = json.loads(line[len("RESULT "):])
    if out is None:
        emit("bench_overlap/ERROR,0,'no RESULT line'")
        if check:
            raise SystemExit(1)
        return

    serial, bucketed = out["serial"], out["bucketed"]
    artifact_path = os.path.join(repo, "BENCH_overlap.json")
    committed = None
    if os.path.exists(artifact_path):
        with open(artifact_path) as f:
            committed = json.load(f)
    artifact = {
        "mesh": {"data": 2, "model": 2},
        "arch": "granite-8b (smoke)",
        "codec": "packed8",
        "serial": serial,
        "bucketed": bucketed,
    }
    with open(artifact_path, "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)

    emit(
        f"overlap/serial,{serial['n_int_dp_collectives']},"
        f"dp_int_bytes={serial['dp_int_bytes']:.0f}"
        f";eqns={serial['collective_eqns']}"
        f";static_eqns={serial['static']['declared_eqns']}"
        f";hidden={serial['static']['hidden_fraction']}"
        f";inter={serial['static']['interleavable_fraction']}"
    )
    emit(
        f"overlap/bucketed,{bucketed['n_int_dp_collectives']},"
        f"buckets={bucketed['n_buckets']}"
        f";manifest_bytes={bucketed['manifest_bytes']}"
        f";eqns={bucketed['collective_eqns']}"
        f";static_eqns={bucketed['static']['declared_eqns']}"
        f";hidden={bucketed['static']['hidden_fraction']}"
        f";inter={bucketed['static']['interleavable_fraction']}"
    )

    if check:
        failures = []
        if serial["n_int_dp_collectives"] != 1:
            failures.append(
                f"serial route should put ONE monolithic integer psum on the "
                f"wire, found {serial['collective_eqns']}"
            )
        if bucketed["n_buckets"] < 2:
            failures.append(
                f"bucketed route produced {bucketed['n_buckets']} bucket(s); "
                "nothing to interleave"
            )
        if bucketed["n_int_dp_collectives"] < 2:
            failures.append(
                f"bucketed route emitted {bucketed['n_int_dp_collectives']} "
                "integer dp collectives; expected >= 2 interleavable units"
            )
        if bucketed["manifest_bytes"] != serial["dp_int_bytes"]:
            failures.append(
                f"bucketing changed the per-worker wire payload: manifest "
                f"{bucketed['manifest_bytes']} B vs serial psum "
                f"{serial['dp_int_bytes']:.0f} B"
            )
        # static == measured, per route: the analyzer's declared transport
        # must land on exactly the collectives the jaxpr counter sees
        for name, route in (("serial", serial), ("bucketed", bucketed)):
            st = route.get("static") or {}
            if st.get("declared_eqns") != route["n_int_dp_collectives"]:
                failures.append(
                    f"{name} route: static transport model declares "
                    f"{st.get('declared_eqns')} wire collective(s) but the "
                    f"jaxpr counter measured {route['n_int_dp_collectives']}"
                )
            if not st.get("ok", False):
                failures.append(
                    f"{name} route: static audit not clean: {st.get('rules')}"
                )
        if bucketed["manifest_ring_eqns"] != bucketed["n_int_dp_collectives"]:
            failures.append(
                f"BucketManifest.ring_collectives declares "
                f"{bucketed['manifest_ring_eqns']} eqn(s) but the jaxpr "
                f"counter measured {bucketed['n_int_dp_collectives']}"
            )
        if bucketed["static"]["interleavable_fraction"] != 1.0:
            failures.append(
                f"bucketed route's static roofline says only "
                f"{bucketed['static']['interleavable_fraction']} of wire "
                f"bytes are interleavable; the bucketed ring promises 1.0"
            )
        # committed-artifact gate: fresh STATIC counts must match the
        # committed measured counts (12 bucketed vs 1 serial on this mesh)
        if committed is not None:
            for name, route in (("serial", serial), ("bucketed", bucketed)):
                was = (committed.get(name) or {}).get("n_int_dp_collectives")
                now = route["static"]["declared_eqns"]
                if was is not None and was != now:
                    failures.append(
                        f"{name} route: static count {now} drifted from the "
                        f"committed BENCH_overlap.json count {was} — a "
                        f"transport change must regenerate the artifact "
                        f"explicitly"
                    )
        ref_path = os.path.join(repo, "BENCH_comm_volume.json")
        if os.path.exists(ref_path):
            with open(ref_path) as f:
                ref = json.load(f)
            ref_bytes = ref.get("codecs", {}).get("packed8", {}).get("dp_int")
            if ref_bytes is not None and bucketed["manifest_bytes"] != ref_bytes:
                failures.append(
                    f"per-step wire bytes drifted vs BENCH_comm_volume.json: "
                    f"{bucketed['manifest_bytes']} != packed8 dp_int "
                    f"{ref_bytes:.0f}"
                )
        if failures:
            emit(f"overlap/CHECK_FAILED,0,{failures!r}")
            raise SystemExit(1)
        emit("overlap/CHECK_OK,1,bucketed route interleavable at unchanged bytes")


if __name__ == "__main__":
    main(check="--check" in sys.argv[1:])
