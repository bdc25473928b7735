"""Compile every Pallas kernel, and the fused train step, for a TPU v5e chip
that is described rather than attached.

The TPU compiler refuses what interpret mode runs happily: vector loads from
an unplaced memory space, blocks that break the (8, 128) tiling, casts Mosaic
has no rule for, more VMEM than a kernel may use. The kernels compile here at
granite-8b widths (one 4096 x 14336 FFN leaf), through the ``kernels.ops``
wrappers that choose their blocks. Nothing runs; these are compiles only.

The topology is described inside a fixture, so only the test worker that
runs this file loads the TPU library. ``jax.default_backend()`` still says
CPU here, so the tests steer the kernels out of interpret mode themselves.
"""
import base64
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec, SingleDeviceSharding

from repro.configs import ShapeConfig, get_arch, smoke_config
from repro.core import make_compressor
from repro.kernels import ops
from repro.launch.step import build_train_step
from repro.optim import adamw, sgd
from repro.optim.schedules import constant
from repro.optim.zero1 import zero1_update
from repro.parallel import collectives as coll

ROWS, COLS = 4096, 14336  # granite-8b d_model x d_ff
N_WORKERS, BITS = 4, 8
K = 32 // BITS
WORDS = -(-ROWS * COLS // K)
DANUBE_HEAD = (3840, 32000)  # h2o-danube-3-4b d_model x vocab
DANUBE_KV = (2, 3840, 960)  # two layers' wk: d_model x 8 KV heads of 120

# HLO ops that must not surround the packed fused kernel of a leaf that
# takes its native row-chunk view. The (2, 3840, 960) leaf may be held in
# a {1,2,0} layout (960 is not a multiple of 128), which costs one
# relayout copy each way, but never a pad.
NATIVE_VIEW_FREE_OF = {
    "fused_unpack_apply_sgd": ("pad", "slice", "copy"),
    "fused_unpack_apply_adamw": ("pad", "slice", "copy"),
    "fused_unpack_apply_sgd_danube_head": ("pad", "slice", "copy"),
    "fused_unpack_apply_sgd_danube_kv": ("pad",),
}


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compile cache off: a compile
    for a described chip is written to it but cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    leaf = ((ROWS, COLS), jnp.float32)
    words = ((WORDS,), jnp.int32)
    sgd_scalars = ((5,), jnp.float32)
    adamw_scalars = ((11,), jnp.float32)

    def unpack_sgd(w, p, m, s):
        return ops.fused_unpack_apply(
            w, p, (m,), s, kernel="sgd", bits=BITS, n_summed=N_WORKERS,
            interpret=False)

    return {
        "int_compress": (
            lambda x, a, k: ops.int_compress(
                x, a, k, n_workers=N_WORKERS, bits=BITS, interpret=False),
            [leaf, ((), jnp.float32), ((2,), jnp.uint32)],
        ),
        "pack_words": (
            lambda x: ops.pack_words(
                x, bits=BITS, n_workers=N_WORKERS, interpret=False),
            [((ROWS, COLS), jnp.int32)],
        ),
        "unpack_words": (
            lambda w: ops.unpack_words(
                w, (ROWS, COLS), bits=BITS, n_summed=N_WORKERS,
                interpret=False),
            [words],
        ),
        "fused_apply_sgd": (
            lambda i, p, m, s: ops.fused_apply(
                i, p, (m,), s, kernel="sgd", interpret=False),
            [((ROWS, COLS), jnp.int8), leaf, leaf, sgd_scalars],
        ),
        "fused_apply_adamw": (
            lambda i, p, m, v, s: ops.fused_apply(
                i, p, (m, v), s, kernel="adamw", interpret=False),
            [((ROWS, COLS), jnp.int8), leaf, leaf, leaf, adamw_scalars],
        ),
        "fused_unpack_apply_sgd": (
            unpack_sgd, [words, leaf, leaf, sgd_scalars],
        ),
        "fused_unpack_apply_adamw": (
            lambda w, p, m, v, s: ops.fused_unpack_apply(
                w, p, (m, v), s, kernel="adamw", bits=BITS,
                n_summed=N_WORKERS, interpret=False),
            [words, leaf, leaf, leaf, adamw_scalars],
        ),
        "fused_unpack_apply_sgd_danube_head": (
            unpack_sgd,
            [((int(np.prod(DANUBE_HEAD)) // K,), jnp.int32),
             (DANUBE_HEAD, jnp.float32), (DANUBE_HEAD, jnp.float32),
             sgd_scalars],
        ),
        "fused_unpack_apply_sgd_danube_kv": (
            unpack_sgd,
            [((int(np.prod(DANUBE_KV)) // K,), jnp.int32),
             (DANUBE_KV, jnp.float32), (DANUBE_KV, jnp.float32),
             sgd_scalars],
        ),
        "block_sq_norms": (
            lambda x: ops.block_sq_norms(x, 8, interpret=False), [leaf],
        ),
        "sq_norm": (lambda x: ops.sq_norm(x, interpret=False), [leaf]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_cases()[name]
    compiled = jax.jit(fn).lower(
        *[_sds(one_chip, s, dt) for s, dt in args]
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if name in NATIVE_VIEW_FREE_OF:
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        found = {op: len(re.findall(rf"\s{op}\(%", text))
                 for op in NATIVE_VIEW_FREE_OF[name]}
        assert not any(found.values()), found


@pytest.fixture
def kernels_compiled(monkeypatch):
    """Kernels out of interpret mode. The wrappers decide it while they
    trace, so no trace made under the CPU's answer may be reused here, and
    none made here may be reused after."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    yield
    jax.clear_caches()


@pytest.mark.parametrize("opt", ["sgd", "adamw"])
def test_fused_train_step_compiles_for_v5e(topo, kernels_compiled, opt):
    """The smoke-width fused route on a one-chip mesh of the described
    devices: the step compiles and the Pallas kernel is in it."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    cfg = smoke_config(get_arch("granite-8b"))
    base_opt = sgd(momentum=0.9) if opt == "sgd" else adamw()
    art = build_train_step(
        cfg, mesh, ShapeConfig("t", 64, 2, "train"),
        compressor=make_compressor("intsgd8", wire="packed8"),
        base_opt=base_opt, lr_schedule=constant(0.1),
        param_dtype=jnp.float32, fused=True, clip_norm=1.0,
    )
    compiled = art.jitted["compressed"].lower(*art.arg_structs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_held_experts_compile_for_v5e(one_chip, kernels_compiled):
    """The held experts' grouped SwiGLU, forward and backward, over one
    pass of the layer's loop at DeepSeek-V2-Lite widths: 8,192 grouped rows
    of d_model 2048 through 8 experts of width 1408 (megablox's gmm and
    tgmm, tiled by ``moe._tiling``)."""
    from repro.models import moe

    def loss(xs, sizes, wg, wu, wd):
        return jnp.sum(moe._grouped_swiglu(xs, sizes, wg, wu, wd)
                       .astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 2, 3, 4))
    args = [((moe.CHUNK, 2048), jnp.bfloat16), ((8,), jnp.int32),
            ((8, 2048, 1408), jnp.float32), ((8, 2048, 1408), jnp.float32),
            ((8, 1408, 2048), jnp.float32)]
    text = jax.jit(grad).lower(
        *[_sds(one_chip, s, dt) for s, dt in args]).compile().as_text()
    # three products forward; their data and weight gradients backward
    assert text.count('custom_call_target="tpu_custom_call"') >= 6


def test_latent_attention_flash_compiles_for_v5e(one_chip, kernels_compiled):
    """MLA's causal attention, forward and backward, at DeepSeek-V2-Lite
    widths over 8,192 positions: 16 heads, q and k 192 wide and v 128,
    padded to 256 for the flash kernel, in blocks of 512 rows."""
    from repro.models import mla

    def loss(q, k, v):
        pos = jnp.broadcast_to(jnp.arange(8192, dtype=jnp.int32), (1, 8192))
        out = mla._attend(q, k, v, pos, 1.59)
        return jnp.sum(out.astype(jnp.float32))

    qk, vv = ((1, 8192, 16, 192), jnp.bfloat16), ((1, 8192, 16, 128), jnp.bfloat16)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *[_sds(one_chip, s, dt) for s, dt in (qk, qk, vv)]).compile().as_text()
    # the Mosaic modules the custom calls carry name their kernels: the
    # forward (run again for the backward's residuals), dk/dv and dq
    bodies = b"".join(base64.b64decode(b) for b in
                      re.findall(r'"body":"([A-Za-z0-9+/=]+)"', text))
    for kernel in (b"_flash_attention_kernel", b"_flash_attention_dkv_kernel",
                   b"_flash_attention_dq_kernel"):
        assert kernel in bodies, kernel


@pytest.mark.parametrize("n_dp", [1, 4])
def test_zero1_update_compiles_for_v5e(topo, n_dp):
    """ZeRO-1's momentum-SGD update of one granite FFN leaf (4096 x 14336
    f32) and the alpha rule's sum of squares of its change, over n_dp
    members of a data axis of the described chips. Held as blocks of the
    leaf's own rows, neither side of the update relayouts it into a row
    (no copy to a 1-D or T(1,128) layout), one member reads and writes at
    most 36 bytes a parameter (the flat rows took 80), and four members
    compile in seconds, not the minutes of a large 1-D reshape."""
    base = sgd(momentum=0.9, weight_decay=1e-4)
    mesh = Mesh(np.array(topo.devices[:n_dp]), ("data",))
    block = PartitionSpec("data", None, None)

    def body(p, master, mom, g):
        new_p, st = zero1_update(
            base, {"master": master, "base": mom}, g, jnp.float32(0.1),
            dp_axes=("data",), dp_index=lax.axis_index("data"), n_dp=n_dp,
            tp=1, param_dtype=jnp.float32, params_like=p)
        dx = jnp.sum(jnp.square(new_p - p))
        return new_p, st["master"], st["base"], dx

    rep = PartitionSpec()
    fn = coll.sharded_jit(body, mesh, (rep, block, block, rep),
                          (rep, block, block, rep), donate=(0, 1, 2))
    leaf = jax.ShapeDtypeStruct((ROWS, COLS), jnp.float32)
    state = jax.ShapeDtypeStruct((n_dp, ROWS // n_dp, COLS), jnp.float32)
    t0 = time.perf_counter()
    compiled = fn.lower(leaf, state, state, leaf).compile()
    assert time.perf_counter() - t0 < 60
    copies = re.findall(r"= (.*?) copy(?:-start)?\(", compiled.as_text())
    rows = [c for c in copies if "T(1,128)" in c or re.match(r"f32\[\d+\]", c)]
    assert not rows, rows
    if n_dp == 1:
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        assert cost["bytes accessed"] <= 36 * ROWS * COLS
