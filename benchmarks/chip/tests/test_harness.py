"""Tests of the benchmark's harness, on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

They live beside the benchmark, outside the repository's ``tests/``, so the
tier-1 suite does not run them.
"""
import gzip
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, HERE]

import bench  # noqa: E402
import hlo  # noqa: E402
import tiny  # noqa: E402
import trace_reduce as tr  # noqa: E402

def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# cost functions against hand counts
# ---------------------------------------------------------------------------
def test_granite_costs_match_hand_counts():
    c = _json(CHIP, "configs", "granite-8b.json")
    t = _json(CHIP, "traffic", "intsgd8_zero1_b2s2048.json")
    step = bench.cost("train_step")
    fused = bench.cost("fused_update")
    # one layer: q, k, v, o = 4096*(4096 + 1024 + 1024) + 4096*4096,
    # feed-forward 3*4096*14336; plus two norms; embedding and head 49152*4096
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    params = layer + 2 * 4096 + 2 * 49152 * 4096 + 4096
    assert params == 620_769_280
    assert params // 4 == 155_192_320  # packed8: four fields a word
    ops, nbytes = fused(c, t, 1)
    assert nbytes == 17 * params and ops == 8 * params  # 1 B field + 16 B
    flops, _ = step(c, t, 1)
    matmul = layer + 4096 * 49152
    attn = 12 * (2049 / 2) * 32 * 128  # mean keys over a causal 2048
    assert flops == pytest.approx(4096 * (6 * matmul + attn), rel=1e-12)


def test_window_bounds_the_attention_count():
    step = bench.cost("train_step")
    c = dict(_json(CHIP, "configs", "h2o-danube-3-4b.json"))
    t = _json(CHIP, "traffic", "intsgd8_fused_b1s2048.json")
    full, _ = step(c, t, 1)
    c["window"] = 1024
    windowed, _ = step(c, t, 1)
    assert windowed < full


def test_a_missing_family_names_its_file():
    with pytest.raises(bench.BenchmarkError,
                       match="benchmarks/chip/families/nosuch.py"):
        bench.family("nosuch")


# the keys the program's configuration was built from before every
# ModelConfig field of the file was passed through
DENSE_KEYS = ("name", "family", "n_layers", "d_model", "n_heads",
              "n_kv_heads", "d_ff", "vocab", "head_dim", "window",
              "rope_theta", "tie_embeddings", "qkv_bias")


@pytest.mark.parametrize("name", ["granite-8b", "h2o-danube-3-4b"])
def test_dense_model_configs_are_unchanged(name):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from program import model_config
    from repro.configs.base import ModelConfig

    c = _json(CHIP, "configs", f"{name}.json")
    assert model_config(c) == ModelConfig(**{k: c[k] for k in DENSE_KEYS})


def test_unknown_device_has_no_peaks():
    assert bench.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(bench.BenchmarkError, match="no peaks"):
        bench.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# the trace reduction, on events laid out by hand
# ---------------------------------------------------------------------------
def test_union_and_gaps_of_intervals():
    ops = [tr.Op("a", 0.0, 2.0), tr.Op("b", 1.0, 3.0), tr.Op("c", 5.0, 6.0)]
    assert tr.union([(o.start, o.end) for o in ops]) == [(0.0, 3.0), (5.0, 6.0)]
    assert tr.busy(ops, 0.0, 10.0) == 4.0
    assert tr.busy(ops, 2.5, 5.5) == 1.0
    assert tr.gaps(ops, -1.0, 7.0) == [(-1.0, 0.0), (3.0, 5.0), (6.0, 7.0)]


def test_idle_gaps_take_the_host_span_they_fall_in():
    ops = [tr.Op("x", 0.0, 1.0), tr.Op("y", 2.0, 3.0)]
    spans = [("step", 0.0, 0.1), ("loss_read", 0.1, 1.2),
             ("data", 1.2, 1.9), ("step", 1.9, 2.0)]
    t = tr.Trace([ops], spans, (0.0, 3.0))
    assert tr.idle_by_span(t) == pytest.approx({"data": 1.0})


# ---------------------------------------------------------------------------
# the trace reduction, on one step recorded on the chip
# ---------------------------------------------------------------------------
RECORDED = os.path.join(HERE, "data", "danube_step.json.gz")


def _recorded():
    """The fixture as the harness would hold it: a Trace of chip 0's ops
    named by op_name, the host spans, and the step's Pallas custom calls."""
    with gzip.open(RECORDED, "rt") as f:
        fx = json.load(f)
    ops = sorted((tr.Op(tr.op_name(n), s * 1e-9, e * 1e-9)
                  for n, s, e in fx["ops"]), key=lambda o: o.start)
    spans = [(n, s * 1e-9, e * 1e-9) for n, s, e in fx["spans"]]
    trace = tr.Trace([ops], spans, (0.0, fx["window_ns"][1] * 1e-9))
    return fx, trace, hlo.instructions(fx["hlo"])


def _sweep_busy(intervals):
    """Busy time by a sweep over start and end points, independent of
    trace_reduce's union."""
    points = sorted([(s, 1) for s, _ in intervals] + [(e, -1) for _, e in intervals],
                    key=lambda p: (p[0], -p[1]))
    busy, depth, since = 0.0, 0, None
    for t, d in points:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_op_names_are_the_compiled_instructions():
    fx, trace, instrs = _recorded()
    raw = [n for n, _, _ in fx["ops"] if n.startswith("%fused_unpack_apply_2d")]
    assert raw and all(" = " in n for n in raw)  # the TPU's whole-text names
    names = {tr.op_name(n) for n in raw}
    assert names == set(instrs)
    assert {i["kernel"] for i in instrs.values()} == {"_unpack_sgd_kernel"}
    assert tr.op_name("fusion.2") == "fusion.2"


def test_recorded_step_kernel_events_by_name():
    fx, trace, instrs = _recorded()
    ctx = {"trace": trace, "instrs": instrs, "steps": 1}
    kernel = bench.reader("fused_update_ms")(ctx)
    by_hand = [(e - s) * 1e-6 for n, s, e in fx["ops"]
               if n.startswith("%fused_unpack_apply_2d")]
    # one kernel call per parameter leaf of danube's two stacked layers:
    # embed, lm_head, ln_f and the layers' ln1, ln2, wq, wk, wv, wo,
    # w_gate, w_up, w_down
    assert len(by_hand) == 12
    assert kernel == pytest.approx(sum(by_hand), rel=1e-12)


def test_recorded_step_busy_and_idle():
    fx, trace, _ = _recorded()
    a, b = trace.window
    ops = trace.devices[0]
    busy = tr.busy(ops, a, b)
    assert busy == pytest.approx(_sweep_busy([(o.start, o.end) for o in ops]),
                                 abs=1e-9)
    assert 0.5 * (b - a) < busy < b - a
    idle = tr.idle_by_span(trace)
    assert sum(idle.values()) == pytest.approx((b - a) - busy, abs=1e-9)
    assert set(idle) <= set(tr.SPANS) | {tr.IDLE_OUTSIDE}
    # the chip waits while the host makes the next batch
    assert idle["data"] > 0
    share = bench.reader("device_idle")({"trace": trace})
    assert share == pytest.approx(100.0 * (1 - busy / (b - a)))


def test_a_declared_metric_with_nothing_to_read_fails_the_run():
    import layers

    ops = [tr.Op("fusion.1", 0.0, 1.0)]
    ctx = {"trace": tr.Trace([ops], [("step", 0.0, 1.0)], (0.0, 1.0)),
           "instrs": {"fusion.1": {"opcode": "fusion", "arrays": [],
                                   "computation": "main"}},
           "steps": 1}
    cell = tiny.cell()
    declared = dict(name="fused_update_ms", unit="ms", moves="tokens_per_s")
    cell = bench.Cell(**dict(cell.__dict__, per_layer=(declared,)))
    with pytest.raises(layers.MetricMissing, match="fused_update_ms"):
        layers.read_all(cell, ctx)


# ---------------------------------------------------------------------------
# the compiled program's collectives
# ---------------------------------------------------------------------------
HLO = """\
HloModule m

%fused_computation.1 (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  ROOT %ar = s32[8]{0} all-reduce(%p), replica_groups={{0,1}}
}

ENTRY %main.1 (a: s32[400], b: f32[100], c: f32[2]) -> f32[400] {
  %a = s32[400]{0} parameter(0)
  %psum.12 = s32[400]{0:T(1024)} all-reduce(%a), channel_id=1, metadata={op_name="jit(step)/shard_map/psum"}
  %all-gather.3 = f32[400]{0} all-gather(%b), dimensions={0}
  %psum.13 = f32[]{:T(128)} all-reduce(%c), channel_id=2
  %ag-start = (f32[100], f32[400]) all-gather-start(%b), dimensions={0}
  %ag-done = f32[400]{0} all-gather-done(%ag-start)
  %fusion.7 = s32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
}
"""


def test_collectives_by_kind_and_element_type():
    ins = hlo.instructions(HLO)
    assert hlo.collective_of("psum.12", ins) == ("all-reduce", "s32", 400)
    assert hlo.collective_of("psum.13", ins) == ("all-reduce", "f32", 1)
    assert hlo.collective_of("all-gather.3", ins) == ("all-gather", "f32", 400)
    assert hlo.collective_of("fusion.7", ins) == ("all-reduce", "s32", 8)
    assert hlo.collective_of("ag-done", ins)[0] == "all-gather"
    assert ins["psum.12"]["op_name"] == "jit(step)/shard_map/psum"


# ---------------------------------------------------------------------------
# found by name: a new cell, configuration and metric need no edit
# ---------------------------------------------------------------------------
def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


TOY_FAMILY = """\
import bench

_dense = bench.family("dense")
init_params, loss_fn = _dense.init_params, _dense.loss_fn


def ops_per_token(c, seq):
    return 2 * _dense.ops_per_token(c, seq) + c["n_experts"]
"""

FOUND = """\
import json, sys
import numpy as np
sys.path.insert(0, 'benchmarks/chip')
import bench, check, program
c = bench.load_cell('tiny-cell')
toy = bench.load_cell('toy-cell')
mc = program.model_config(toy.config)
rng = np.random.default_rng(5)
batches = []
for _ in range(3):
    t = rng.integers(0, 256, (2, 32), np.int32)
    batches.append((t, np.roll(t, -1, axis=1)))
tokens = toy.traffic['batch_per_chip'] * toy.traffic['seq_len']
print(json.dumps({
    'tiny': [c.config['d_model'], c.traffic['seq_len'], c.limits['loss']],
    'per_layer': [m['name'] for m in c.per_layer],
    'steps_traced': bench.reader('steps_traced')({'steps': 3}),
    'model_config': [mc.family, mc.n_experts, mc.top_k, mc.n_shared_experts,
                     mc.kv_lora],
    'cost': bench.cost('train_step')(toy.config, toy.traffic, 1)[0],
    'dense_ops': bench.family('dense').ops_per_token(toy.config, 32) * tokens,
    'toy_ref': check.reference_readings(toy, 7, batches),
    'dense_ref': check.reference_readings(c, 7, batches),
}))
"""


def test_new_cell_and_metric_are_found_without_editing(tmp_path):
    """A cell, a configuration of a new family, its family file and a
    per-layer metric join as new files and entries: no file the benchmark
    has changes. The program keeps the family's keys, the cost counts the
    family's operations, and the reference runs the family's model."""
    root = tmp_path / "checkout"
    shutil.copytree(CHIP, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    before = _digests(root / "benchmarks" / "chip")
    chip = root / "benchmarks" / "chip"
    toy = dict(tiny.CONFIG, name="toy", family="toy", n_experts=8, top_k=2,
               n_shared_experts=1, kv_lora=32)
    (chip / "configs" / "tiny.json").write_text(json.dumps(tiny.CONFIG))
    (chip / "configs" / "toy.json").write_text(json.dumps(toy))
    (chip / "families" / "toy.py").write_text(TOY_FAMILY)
    (chip / "traffic" / "tiny_job.json").write_text(json.dumps(tiny.TRAFFIC))
    for cell in ("tiny-cell", "toy-cell"):
        (chip / "cells" / f"{cell}.json").write_text(
            json.dumps({"limits": tiny.LIMITS}))
    (chip / "metrics" / "steps_traced.py").write_text(
        "def read(ctx):\n    return ctx['steps']\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("tiny", "toy"):
        b["configs"].append({"name": name, "source": "a test", "reduced": [],
                             "file": f"benchmarks/chip/configs/{name}.json",
                             "why": "test"})
        b["workloads"].append({"name": f"{name}-cell", "config": name,
                               "traffic": "tiny_job", "chips": 1,
                               "why": "test"})
    b["per_layer"].append({"name": "steps_traced", "unit": "steps",
                           "better": "higher", "source": "device_trace",
                           "layer": "device", "moves": "tokens_per_s",
                           "workloads": ["tiny-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", FOUND], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["tiny"] == [64, 32, tiny.LIMITS["loss"]]
    assert "steps_traced" in out["per_layer"] and out["steps_traced"] == 3
    assert out["model_config"] == ["toy", 8, 2, 1, 32]
    assert out["cost"] == 2 * out["dense_ops"] + 8 * 64
    assert out["toy_ref"] == out["dense_ref"]
    after = _digests(chip)
    assert all(after[k] == v for k, v in before.items())


# ---------------------------------------------------------------------------
# the run refuses what is not a chip
# ---------------------------------------------------------------------------
def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "granite8b-intsgd-zero1-1chip", "--seed", "2147483659",
         "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode == 2 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
