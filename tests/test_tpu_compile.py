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
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import ShapeConfig, get_arch, smoke_config
from repro.core import make_compressor
from repro.kernels import ops
from repro.launch.step import build_train_step
from repro.optim import adamw, sgd
from repro.optim.schedules import constant

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
