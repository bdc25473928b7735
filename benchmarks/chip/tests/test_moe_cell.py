"""The MoE family in the harness, on the CPU: DeepSeek-V2-Lite's
configuration, its cell, the float baseline cell, their traffic and limits,
and the per-layer metrics of latent attention and the experts are found by
name, as new files and entries only; the readers read their scopes inside
loops and find nothing where a step has none; and a tiny cell of the
family runs, is correct, and the renormalised-top-k fault is caught.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
import dataclasses
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, HERE]

import bench  # noqa: E402
import trace_reduce as tr  # noqa: E402
import tiny  # noqa: E402
from test_stages import FIXTURES, _recorded  # noqa: E402

NEW = "dsv2lite-intsgd-zero1-1chip"
FLOAT = "granite8b-float-zero1-1chip"
MOE_METRICS = ("mla_ms", "routing_ms", "experts_ms", "experts_roofline")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2147483693


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_new_cells_are_found_by_name():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from program import model_config

    cell = bench.load_cell(NEW)
    names = {m["name"] for m in cell.per_layer}
    assert names == set(MOE_METRICS) | {"mfu", "device_idle", "fwd_bwd_ms",
                                        "update_ms"}
    assert cell.chips == 1 and cell.traffic["seq_len"] == 8192
    assert cell.traffic["batch_per_chip"] == 1
    mc = model_config(cell.config)
    assert (mc.family, mc.n_layers, mc.first_k_dense, mc.d_ff_dense) == (
        "moe", 6, 1, 10944)
    assert (mc.n_experts, mc.n_experts_held, mc.top_k, mc.d_ff) == (
        64, 8, 6, 1408)
    assert (mc.norm_topk_prob, mc.kv_lora) == (False, 512)
    assert bench.family(cell.config["family"]).ops_per_token
    for m in MOE_METRICS:
        assert callable(bench.reader(m))
    # the float baseline is granite's job with the compressor taken out
    flt = bench.load_cell(FLOAT)
    base = _json(CHIP, "traffic", "intsgd8_zero1_b2s2048.json")
    changed = {k for k in base if base[k] != flt.traffic[k]}
    assert changed == {"compressor", "wire", "bits"}
    assert (flt.traffic["compressor"], flt.traffic["wire"],
            flt.traffic["bits"]) == ("none", None, None)
    assert flt.config == bench.load_cell("granite8b-intsgd-zero1-1chip").config
    assert set(flt.limits) == set(cell.limits) == {
        "loss", "grad0", "change", "change_median", "window_compiles"}


def test_the_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config.json is in the file under its
    own key; those that differ are the cuts that ``reduced`` lists."""
    c = _json(CHIP, "configs", "deepseek-v2-lite.json")
    entry = [x for x in bench.benchmark()["configs"]
             if x["name"] == "deepseek-v2-lite"][0]
    assert c["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    differ = {k for k, v in c["published"].items() if c[k] != v}
    assert differ == set(c["reduced"])
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (
        6, 8, 12800)
    assert c["published"]["num_hidden_layers"] == 27
    assert c["n_layers"] == c["num_hidden_layers"]
    assert c["n_experts_held"] == c["n_routed_experts"]
    assert c["vocab"] == c["vocab_size"]


def test_moe_costs_match_hand_counts():
    cell = bench.load_cell(NEW)
    c, t = cell.config, cell.traffic
    ops, nbytes = bench.cost("moe_experts")(c, t, 1)
    rows = 8192 * 6 * 8 / 64  # 6,144 routed rows a layer on this chip
    assert rows == 6144
    assert ops == 18 * 2048 * 1408 * rows * 5
    weights = 3 * 8 * 2048 * 1408 * 2
    acts = rows * (2 * 2048 + 3 * 1408) * 2
    assert nbytes == 5 * 3 * (weights + acts)
    # the step: 6 x the weights a token meets, plus latent attention's two
    # products over the mean causal keys (8193 / 2)
    attn = (2048 * 512 + 2048 * 64 + 2 * 512 * 2048 + 2048 * 16 * 192
            + 2048 * 2048)
    moe = 2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408
    matmul = 6 * attn + 3 * 2048 * 10944 + 5 * moe + 2048 * 12800
    per_token = 6 * matmul + 6 * (8193 / 2) * 16 * (128 + 64 + 128) * 6
    flops, _ = bench.cost("train_step")(c, t, 1)
    assert flops == pytest.approx(8192 * per_token, rel=1e-12)
    params = 81e6 + 5 * 100.4e6 + 52.4e6  # the configuration's deployment
    assert params == pytest.approx(635.4e6, rel=1e-3)


@pytest.mark.parametrize("fixture", sorted(FIXTURES))
@pytest.mark.parametrize("metric", MOE_METRICS)
def test_moe_readers_find_nothing_without_their_scopes(fixture, metric):
    ctx = _recorded(FIXTURES[fixture])
    ctx.update(cell=bench.load_cell(NEW), peaks=bench.peaks("TPU v5 lite"),
               cost=bench.cost)
    assert bench.reader(metric)(ctx) is None


def _laid_out():
    """A step laid out by hand: a layer loop in fwd_bwd whose body runs one
    latent attention op, two routing ops and a grouped-product kernel."""
    pre = "jit(step)/fwd_bwd/jvp()/while/body/closed_call"
    named = {
        "while.1": "jit(step)/fwd_bwd/jvp()/while",
        "fusion.1": f"{pre}/mla/dot_general",
        "fusion.2": f"{pre}/moe_route/top_k",
        "fusion.3": f"{pre}/while/body/moe_route/scatter-add",
        "gmm.1": f"{pre}/while/body/moe_experts/jit(gmm)/pallas_call",
        "fusion.4": "jit(step)/update/add",
    }
    ms = 1e-3
    ops = [tr.Op("while.1", 0.0, 50 * ms), tr.Op("fusion.1", 1 * ms, 20 * ms),
           tr.Op("fusion.2", 20 * ms, 21 * ms), tr.Op("gmm.1", 21 * ms, 41 * ms),
           tr.Op("fusion.3", 41 * ms, 41.5 * ms),
           tr.Op("fusion.4", 50 * ms, 52 * ms)]
    instrs = {n: {"opcode": "fusion", "arrays": [], "computation": "body",
                  "op_name": o} for n, o in named.items()}
    instrs["while.1"]["computation"] = instrs["fusion.4"]["computation"] = "main"
    return {"trace": tr.Trace([ops], [], (0.0, 52 * ms), 1), "instrs": instrs,
            "steps": 1, "entry": "main", "cell": bench.load_cell(NEW),
            "peaks": bench.peaks("TPU v5 lite"), "cost": bench.cost}


def test_moe_readers_read_their_scopes_inside_the_layer_loop():
    ctx = _laid_out()
    read = lambda m: bench.reader(m)(ctx)
    assert read("mla_ms") == pytest.approx(19.0)
    assert read("routing_ms") == pytest.approx(1.5)
    assert read("experts_ms") == pytest.approx(20.0)
    ops, nbytes = bench.cost("moe_experts")(ctx["cell"].config,
                                            ctx["cell"].traffic, 1)
    assert ops / 197e12 > nbytes / 819e9  # the operations bound it
    assert read("experts_roofline") == pytest.approx(
        100 * ops / 197e12 / 20e-3)  # 8.09 ms at peak over 20 ms: 40.5%
    assert bench.reader("fwd_bwd_ms")(ctx) == pytest.approx(50.0)


# ---------------------------------------------------------------------------
# a tiny cell of the family, end to end on the CPU
# ---------------------------------------------------------------------------
TINY_MOE = dict(
    tiny.CONFIG, name="tiny-moe", family="moe", n_layers=3, d_ff=32,
    n_kv_heads=4, n_experts=8, n_experts_held=4, top_k=3, n_shared_experts=2,
    norm_topk_prob=False, aux_loss_alpha=0.001,
    first_k_dense=1, d_ff_dense=96, kv_lora=32, yarn_factor=40.0,
    yarn_original_max_position=4096, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
)
# By calibrate.py at this size on the CPU, as the cells' rule sets them:
# the sound program's largest readings over 6 seeds were loss 4.0e-4,
# grad0 3.6e-3, change 3.1e-3, change_median 6.5e-4; the float8 control's
# smallest over 2 seeds 1.9e-3, 1.7e-2, 9.6e-3, 3.3e-3; each limit 60% of
# the way up on a log scale.
TINY_MOE_LIMITS = {"loss": 1.0e-3, "grad0": 9.1e-3, "change": 6.0e-3,
                   "change_median": 1.45e-3, "window_compiles": 0}


def test_tiny_moe_cell_is_correct_and_catches_renormalising():
    import check
    import run

    cell = dataclasses.replace(tiny.cell(), name="tiny-moe", config=TINY_MOE,
                               limits=TINY_MOE_LIMITS)
    r = run.run_cell(cell, SEED, 0.5, False, device=CPU)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    # the fault: top-k renormalised where the configuration says not to
    import numpy as np

    rng = np.random.default_rng(5)
    batches = []
    for _ in range(3):
        t = rng.integers(0, 256, (2, 32), np.int32)
        batches.append((t, np.roll(t, -1, axis=1)))
    sound = check.reference_readings(cell, SEED, batches)
    renorm = dataclasses.replace(
        cell, config=dict(cell.config, norm_topk_prob=True))
    got = check.judge(cell, check.numbers(
        check.reference_readings(renorm, SEED, batches), sound))
    assert not all(c["ok"] for c in got.values()), got
