"""DeepSeek-V2-style MoE and latent attention against the benchmark's plain
reference (benchmarks/chip/families/moe.py), on the CPU at a smoke size of
the family: the held-experts layer that drops no token, its shares under
expert parallelism, top-k with and without renormalisation, YaRN, and the
train step's expert-row counters and model scopes."""
import contextlib
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks", "chip"))

import bench  # noqa: E402
import reference  # noqa: E402

from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.core import make_compressor  # noqa: E402
from repro.kernels import ops as kernel_ops  # noqa: E402
from repro.launch import stages  # noqa: E402
from repro.launch.step import build_train_step  # noqa: E402
from repro.models import mla, moe  # noqa: E402
from repro.models.common import Axes  # noqa: E402
from repro.models.transformer import init_lm_params, lm_loss_stats  # noqa: E402
from repro.optim import sgd  # noqa: E402
from repro.optim.schedules import constant  # noqa: E402

FAMILY = bench.family("moe")
MM = reference.MATMULS["float32"]

# the family at a smoke size: 1 dense + 2 MoE layers, 4 of 8 experts held,
# top-3, 2 shared experts, latent attention with DeepSeek-V2-Lite's YaRN
SMOKE = dict(
    name="dsv2-smoke", family="moe", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, head_dim=16, d_ff=32, vocab=128, window=None,
    rope_theta=10000.0, tie_embeddings=False, qkv_bias=False, n_experts=8,
    n_experts_held=4, top_k=3, n_shared_experts=2, norm_topk_prob=False,
    aux_loss_alpha=0.001, first_k_dense=1, d_ff_dense=96,
    kv_lora=32, yarn_factor=40.0, yarn_original_max_position=4096,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
)
# Both sides compute in float32 at the highest precision; they differ only
# in the order of their sums (grouped rows and scatter-adds against a masked
# sum over experts, blocked attention), which moves a float32 result by a
# few units in its last place, times the depth of the sums.
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4  # of the leaf's largest entry: gradients sum over tokens


def _cfg(**kw):
    c = dict(SMOKE, **kw)
    fields = {f for f in ModelConfig.__dataclass_fields__} - {"source"}
    return c, ModelConfig(**{k: v for k, v in c.items() if k in fields})


def _batch(vocab, b=2, t=40, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (b, t), 0, vocab)
    return tok, jnp.roll(tok, -1, axis=1)


def _leaf_gaps(got, want):
    return {
        jax.tree_util.keystr(p): float(jnp.max(jnp.abs(a - b))
                                       / (jnp.max(jnp.abs(b)) + 1e-30))
        for (p, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                             jax.tree.leaves(want))
    }


@pytest.mark.parametrize("norm_topk_prob", [False, True])
@pytest.mark.parametrize("chunk", [4096, 24])
def test_program_matches_reference(norm_topk_prob, chunk, monkeypatch):
    """Loss and every gradient leaf on seeded weights; `chunk` 24 takes the
    held experts' rows in several passes of the program's loop."""
    monkeypatch.setattr(moe, "CHUNK", chunk)
    c, cfg = _cfg(norm_topk_prob=norm_topk_prob)
    key = jax.random.PRNGKey(3)
    params = init_lm_params(key, cfg, tp=1, n_shards=1, dtype=jnp.float32)
    ref_params = FAMILY.init_params(c, key)
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(a, b)  # the same keys, the same weights
    tok, labels = _batch(c["vocab"])
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.value_and_grad(
            lambda p: lm_loss_stats(p, {"tokens": tok, "labels": labels},
                                    Axes(), cfg, dtype=jnp.float32),
            has_aux=True)(params)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: FAMILY.loss_fn(p, tok, labels, c, MM))(ref_params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=LOSS_RTOL)
    gaps = _leaf_gaps(grads, ref_grads)
    assert max(gaps.values()) < GRAD_RTOL, gaps
    # every (token, choice) pair on a held expert is a row: about half of
    # them, as 4 of 8 experts are held
    rows = np.asarray(stats["expert_rows"])
    assert rows.shape == (2, 4) and 0 < rows.sum() <= 2 * 80 * 3


@pytest.mark.parametrize("path,t", [("chunked", 64), ("chunked", 40),
                                    ("flash", 256)])
def test_latent_attention_blocks_match_reference(path, t, monkeypatch):
    """MLA with the latent norm and YaRN: output and every weight's gradient
    against the reference's plain attention. "chunked" is the path off the
    chip (40: a length the chunks do not divide); "flash" the chip's Pallas
    kernel, run here in TPU interpret mode, in blocks of 128 rows, so that
    the causal mask crosses blocks and q, k (80 wide) and v (16) are padded
    to 128."""
    interpret = contextlib.nullcontext()
    if path == "flash":
        monkeypatch.setattr(kernel_ops, "_interpret_default", lambda: False)
        monkeypatch.setattr(mla, "FLASH_BLOCK", 128)
        interpret = pltpu.force_tpu_interpret_mode()
    c, cfg = _cfg()
    p = FAMILY._attn(jax.random.PRNGKey(0), c)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, t, c["d_model"]))
    pos = jnp.broadcast_to(jnp.arange(t), (2, t))
    ours = lambda q: mla.mla_train(q, x, pos, Axes(), n_heads_local=4,
                                   head_dim=16, rot=mla.rope_of(cfg))
    theirs = lambda q: FAMILY._mla(q, x, c, MM)
    with jax.default_matmul_precision("highest"), interpret:
        traced = str(jax.make_jaxpr(ours)(p))
        assert ("pallas_call" in traced) == (path == "flash")
        np.testing.assert_allclose(ours(p), theirs(p), rtol=1e-5, atol=1e-6)
        gaps = _leaf_gaps(jax.grad(lambda q: jnp.sum(ours(q) ** 2))(p),
                          jax.grad(lambda q: jnp.sum(theirs(q) ** 2))(p))
    assert max(gaps.values()) < GRAD_RTOL, gaps


def test_renormalising_changes_the_result():
    """The fault the cell's check plants: top-k renormalised where the
    configuration says not to. At this size (3 of 8, the top-3 weights sum
    to about a half) it moves the loss by about 1e-3, far past the
    tolerance."""
    c, cfg = _cfg()
    key = jax.random.PRNGKey(3)
    params = init_lm_params(key, cfg, tp=1, n_shards=1, dtype=jnp.float32)
    tok, labels = _batch(c["vocab"])
    batch = {"tokens": tok, "labels": labels}
    with jax.default_matmul_precision("highest"):
        plain = lm_loss_stats(params, batch, Axes(), cfg, jnp.float32)[0]
        renorm = lm_loss_stats(params, batch, Axes(),
                               _cfg(norm_topk_prob=True)[1], jnp.float32)[0]
    assert abs(float(renorm) - float(plain)) > 30 * LOSS_RTOL * float(plain)


def _layer_inputs(d=64, e=8, f=32, n_shared=2, b=2, t=24, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = lambda k, shape, fan: jax.random.uniform(
        k, shape, jnp.float32, -fan ** -0.5, fan ** -0.5)
    p = {
        "router": u(ks[0], (d, e), d),
        "w_gate": u(ks[1], (e, d, f), d),
        "w_up": u(ks[2], (e, d, f), d),
        "w_down": u(ks[3], (e, f, d), f),
        "shared": {"w_gate": u(ks[4], (d, n_shared * f), d),
                   "w_up": u(ks[5], (d, n_shared * f), d),
                   "w_down": u(ks[6], (n_shared * f, d), n_shared * f)},
    }
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, t, d))
    return p, x


def _ref_layer(p, x, c):
    return np.asarray(FAMILY.moe(p, x, c, MM)[0])


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of 8 experts, 2 each: each routes over all 8 and
    computes its own experts' part; with the shared experts counted once,
    the parts add up to the uncut layer, and to the reference's."""
    p, x = _layer_inputs()
    kw = dict(n_experts=8, top_k=3, norm_topk_prob=False)
    with jax.default_matmul_precision("highest"):
        whole, _, rows = moe.moe_held(p, x, **kw)
        shared = moe.swiglu_mlp(p["shared"], x, Axes())
        parts = []
        for s in range(4):
            share = dict(p, **{w: p[w][2 * s:2 * s + 2]
                               for w in ("w_gate", "w_up", "w_down")})
            out, _, share_rows = moe.moe_held(share, x, first=2 * s, **kw)
            np.testing.assert_array_equal(share_rows, rows[2 * s:2 * s + 2])
            parts.append(out - shared)
        c = dict(SMOKE, n_experts=8, n_experts_held=8, top_k=3, d_model=64)
        ref = _ref_layer(p, x, c)
    summed = sum(parts) + shared
    np.testing.assert_allclose(summed, whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(whole, ref, rtol=1e-5, atol=1e-6)
    assert int(rows.sum()) == 2 * 24 * 3  # all held: every choice is a row


@pytest.mark.parametrize("chunk", [4096, 16])
def test_skewed_router_drops_no_token(chunk, monkeypatch):
    """A router biased so that every token picks held expert 0: half of all
    top-2 choices land on it, far past any capacity a balanced load would
    size, and every token still matches the reference."""
    monkeypatch.setattr(moe, "CHUNK", chunk)
    p, x = _layer_inputs(e=8)
    x = x + 3.0  # a common direction every token shares ...
    p["router"] = p["router"].at[:, 0].set(0.5)  # ... that expert 0 favours
    held = dict(p, **{w: p[w][:4] for w in ("w_gate", "w_up", "w_down")})
    c = dict(SMOKE, n_experts=8, n_experts_held=4, top_k=2, d_model=64)
    with jax.default_matmul_precision("highest"):
        out, _, rows = moe.moe_held(held, x, n_experts=8, top_k=2,
                                    norm_topk_prob=False)
        ref = _ref_layer(held, x, c)
    n = x.shape[0] * x.shape[1]
    assert int(rows[0]) == n  # every token chose expert 0: half the choices
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("skewed", [False, True])
def test_each_pass_groups_all_its_rows(skewed):
    """Each pass of the held experts' loop hands the grouped products all
    of its rows, whatever the load: every held expert but the last gets
    the rows routed to it in that pass, and the last takes the rest, so
    the products visit the same tiles in every pass."""
    n, k, e, held, c = 40, 3, 8, 4, 24
    ids = jax.random.randint(jax.random.PRNGKey(7), (n, k), 0, e)
    if skewed:
        ids = ids.at[:, 0].set(0)
    order, sizes, n_rows = moe._plan(ids, 0, held, n * k)
    assert int(n_rows) % c  # the last pass is part routed, part not
    xf = jnp.zeros((n, 8), jnp.float32)
    w = jnp.ones((n * k,), jnp.float32)
    ends = np.cumsum(np.asarray(sizes))
    starts = ends - np.asarray(sizes)
    for i in range(int(moe._n_chunks(c, n_rows))):
        *_, valid, _, _, in_chunk = moe._chunk(
            i, c, k, xf, w, order, sizes, n_rows)
        lo = i * c
        routed = np.clip(ends, lo, lo + c) - np.clip(starts, lo, lo + c)
        np.testing.assert_array_equal(in_chunk[:-1], routed[:-1])
        assert int(in_chunk.sum()) == c
        assert int(valid.sum()) == routed.sum()


def test_yarn_frequencies_and_scale_by_hand():
    """DeepSeek-V2-Lite's rope_scaling (factor 40, original 4096, beta 32
    and 1, mscale = mscale_all_dim = 0.707) over 64 rotary dims, theta
    10000. Correction dims: 64 ln(4096 / (32·2π)) / (2 ln 10000) = 10.47
    and 64 ln(4096 / 2π) / (2 ln 10000) = 22.51, so the ramp runs from
    pair 10 (floor) to pair 23 (ceil)."""
    inv = mla.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    base = 10000.0 ** (-np.arange(32) * 2 / 64)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)  # unstretched
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    # pair 16: 6/13 of the way up the ramp, so 7/13 of the original
    # frequency and 6/13 of the stretched one: 0.01 (7/13 + 6/520)
    assert inv[16] == pytest.approx(0.01 * (7 / 13 + 6 / 520), rel=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1  # 1.26080...
    assert m == pytest.approx(1.260803, abs=1e-6)
    _, cfg = _cfg()
    rot = mla.rope_of(cfg)
    assert rot.score_scale == pytest.approx(m * m, rel=1e-12)  # 1.58962...
    assert rot.rot_scale == 1.0  # mscale / mscale_all_dim
    np.testing.assert_allclose(rot.inv_freq, inv, rtol=1e-6)
    ref_inv, ref_rot, ref_score = FAMILY.rotary(SMOKE)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert (ref_rot, ref_score) == pytest.approx((1.0, m * m), rel=1e-12)


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*.+?\s([a-z][\w\-]*)\(")
_OPNAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def _instructions(text):
    """[(opcode, op_name path components)] of every named instruction."""
    out = []
    for line in text.splitlines():
        m, o = _INSTR.match(line), _OPNAME.search(line)
        if m and o:
            out.append((m.group(1), o.group(1).split("/")))
    return out


def test_step_counts_expert_rows_and_names_model_scopes():
    """The compressed step returns the held experts' rows, the largest and
    the mean, beside (max_int, bits); the model's scopes sit inside
    fwd_bwd, forward and backward, and no scope is a stage's name."""
    assert not set(stages.MODEL_SCOPES) & set(stages.STAGES)
    _, cfg = _cfg(n_layers=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    art = build_train_step(
        cfg, mesh, ShapeConfig("t", 32, 2, "train"),
        compressor=make_compressor("intsgd8"),
        base_opt=sgd(momentum=0.9, weight_decay=1e-4),
        lr_schedule=constant(0.1), param_dtype=jnp.float32,
        wire="packed8", clip_norm=1.0,
    )
    instrs = _instructions(art.jitted["compressed"].lower(*art.arg_structs)
                           .compile().as_text())
    for scope in stages.MODEL_SCOPES:
        inside = [(op, parts) for op, parts in instrs if scope in parts]
        assert inside, scope
        # no other stage holds a model scope, and every matrix product
        # under one is the forward's or the backward's
        assert all({p for p in parts if p in stages.STAGES} <= {"fwd_bwd"}
                   for _, parts in inside), scope
        dots = [parts for op, parts in inside if op == "dot"]
        assert dots and all("fwd_bwd" in parts for parts in dots), scope
        assert any(p.startswith("transpose(") for _, parts in inside
                   for p in parts), scope  # the backward carries it too
    params = init_lm_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    from repro.launch.step import build_init_state

    opt, comp = build_init_state(cfg, mesh,
                                 compressor=make_compressor("intsgd8"),
                                 base_opt=sgd(momentum=0.9, weight_decay=1e-4))(params)
    tok, labels = _batch(cfg.vocab, t=32)
    out = art.jitted["compressed"](params, opt, comp, jnp.int32(1),
                                   jax.random.PRNGKey(1),
                                   {"tokens": tok, "labels": labels})
    max_int, bits, rows_max, rows_mean = out[4]
    # 64 tokens x 3 choices, 4 of 8 experts held: 96 rows expected per
    # layer, 24 per expert
    assert 24 <= float(rows_max) <= 64 and 0 < float(rows_mean) <= float(rows_max)
    assert float(max_int) > 0 and float(bits) > 0


@pytest.mark.parametrize("arch,tp,want", [
    ("deepseek-v2-lite-16b", 1, "held"), ("deepseek-v2-lite-16b", 16, "ep"),
    ("mixtral-8x22b", 1, "held"), ("mixtral-8x22b", 16, "tp"),
])
def test_strategy_follows_the_model_axis_alone(arch, tp, want):
    """One chip's share (tp == 1) always takes the held-experts layer;
    across a model axis the capacity strategies take over, for every MoE
    configuration, and the model's shapes resolve for either."""
    from repro.configs import get_arch
    from repro.models.transformer import resolve_dims

    cfg = get_arch(arch)
    assert moe.pick_strategy(cfg.n_experts, tp) == want
    dims = resolve_dims(cfg, tp, tp)  # one device's shapes
    held = cfg.n_experts_held or cfg.n_experts
    assert dims.e_loc == (held if want == "held" else
                          cfg.n_experts // tp if want == "ep" else cfg.n_experts)
