"""The unified step-builder pipeline: fused Pallas routing parity against
the unfused ZeRO-1 path, the eval builder, and routing validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_arch, smoke_config
from repro.core import make_compressor
from repro.data.synthetic import SyntheticLMData
from repro.launch.step import build_eval_step, build_init_state, build_train_step
from repro.models.transformer import init_lm_params
from repro.optim import adamw, sgd
from repro.optim.schedules import constant


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def _run_steps(cfg, mesh, shape, *, fused, steps=4, compressor="intsgd",
               wire=None, opt=None, lr=0.2):
    comp = make_compressor(compressor)
    opt = opt if opt is not None else sgd(momentum=0.9, weight_decay=1e-4)
    art = build_train_step(
        cfg, mesh, shape, compressor=comp, base_opt=opt,
        lr_schedule=constant(lr), param_dtype=jnp.float32,
        fused=fused, donate=False, wire=wire,
    )
    key = jax.random.PRNGKey(0)
    params = init_lm_params(key, cfg, tp=1, n_shards=1, dtype=jnp.float32)
    params = jax.device_put(params, art.in_shardings[0])
    init = build_init_state(
        cfg, mesh, compressor=comp, base_opt=opt, fused=fused
    )
    opt_state, comp_state = init(params)
    data = SyntheticLMData(cfg.vocab, shape.seq_len, shape.global_batch, seed=0)
    bs = art.in_shardings[5]
    losses = []
    for i in range(steps):
        batch = {k: jax.device_put(v, bs[k]) for k, v in data.batch(i, 0).items()}
        fn = art.jitted["exact"] if i == 0 else art.jitted["compressed"]
        params, opt_state, comp_state, loss, _ = fn(
            params, opt_state, comp_state, jnp.int32(i),
            jax.random.fold_in(key, i), batch,
        )
        losses.append(float(loss))
    return params, losses


@pytest.mark.slow
def test_fused_route_matches_unfused(mesh):
    """The Pallas fused dequantize+SGD routing (CPU interpret mode) must
    match the unfused decode + ZeRO-1 update to ULP-scale tolerance: the
    integer wire is identical, only the update arithmetic is fused."""
    cfg = smoke_config(get_arch("xlstm-125m"))
    shape = ShapeConfig("t", 32, 4, "train")
    p_ref, l_ref = _run_steps(cfg, mesh, shape, fused=False)
    p_fus, l_fus = _run_steps(cfg, mesh, shape, fused=True)
    np.testing.assert_allclose(np.asarray(l_fus), np.asarray(l_ref), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_fus)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=2e-6
        )


@pytest.mark.slow
@pytest.mark.parametrize("fused", [False, True])
def test_packed_wire_matches_dense_route(mesh, fused):
    """build_train_step over the PackedInt wire must match the DenseInt
    route step-for-step (both routes, same integer image — only the
    transport words differ). The 4-device-mesh version of this parity lives
    in test_distributed.py::test_packed_wire_parity_on_mesh."""
    cfg = smoke_config(get_arch("xlstm-125m"))
    shape = ShapeConfig("t", 32, 4, "train")
    p_d, l_d = _run_steps(
        cfg, mesh, shape, fused=fused, compressor="intsgd8", wire="dense8"
    )
    p_p, l_p = _run_steps(
        cfg, mesh, shape, fused=fused, compressor="intsgd8", wire="packed8"
    )
    np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_d), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_d), jax.tree.leaves(p_p)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=2e-6
        )


@pytest.mark.slow
@pytest.mark.parametrize("compressor,wire", [("intsgd8", "packed8"),
                                             ("intdiana", None)])
def test_fused_adamw_matches_unfused(mesh, compressor, wire):
    """The fused decode+AdamW kernel route (bias-corrected moments updated
    in-register) must match the unfused decode + ZeRO-1 AdamW update to
    ULP-scale tolerance, for plain IntSGD and for the IntDIANA shifted
    decode. The 4-device-mesh matrix lives in
    test_distributed.py::test_fused_family_parity_on_mesh."""
    cfg = smoke_config(get_arch("xlstm-125m"))
    shape = ShapeConfig("t", 32, 4, "train")
    p_ref, l_ref = _run_steps(
        cfg, mesh, shape, fused=False, compressor=compressor, wire=wire,
        opt=adamw(), lr=0.01,
    )
    p_fus, l_fus = _run_steps(
        cfg, mesh, shape, fused=True, compressor=compressor, wire=wire,
        opt=adamw(), lr=0.01,
    )
    np.testing.assert_allclose(np.asarray(l_fus), np.asarray(l_ref), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_fus)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=2e-6
        )


@pytest.mark.slow
def test_eval_step_matches_train_loss(mesh):
    """build_eval_step is the train body's forward stage: on identical
    (params, batch) it must report the train step's pre-update loss."""
    cfg = smoke_config(get_arch("xlstm-125m"))
    shape = ShapeConfig("t", 32, 4, "train")
    comp = make_compressor("intsgd")
    opt = sgd(momentum=0.9)
    art = build_train_step(
        cfg, mesh, shape, compressor=comp, base_opt=opt,
        lr_schedule=constant(0.1), param_dtype=jnp.float32, donate=False,
    )
    ev = build_eval_step(cfg, mesh, shape, param_dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    params = init_lm_params(key, cfg, tp=1, n_shards=1, dtype=jnp.float32)
    params = jax.device_put(params, art.in_shardings[0])
    init = build_init_state(cfg, mesh, compressor=comp, base_opt=opt)
    opt_state, comp_state = init(params)
    data = SyntheticLMData(cfg.vocab, shape.seq_len, shape.global_batch, seed=1)
    bs = art.in_shardings[5]
    batch = {k: jax.device_put(v, bs[k]) for k, v in data.batch(0, 0).items()}
    _, _, _, train_loss, _ = art.jitted["exact"](
        params, opt_state, comp_state, jnp.int32(0), key, batch
    )
    eval_loss = ev.jitted["eval"](params, batch)
    np.testing.assert_allclose(
        float(eval_loss), float(train_loss), rtol=1e-6
    )


def test_fused_route_capability_errors(mesh):
    """Pairs outside the fused capability matrix must fail at build time
    naming the MISSING CAPABILITY (Compressor.fused_capable /
    Optimizer.fused_kernel), not a concrete type — the routing contract is
    capability dispatch, so the error has to teach the capability."""
    cfg = smoke_config(get_arch("xlstm-125m"))
    shape = ShapeConfig("t", 32, 4, "train")
    # compressor without wire-level aggregation: names fused_capable and the
    # compressor, not "isinstance of IntSGD"
    with pytest.raises(ValueError, match="fused_capable") as ei:
        build_train_step(
            cfg, mesh, shape, compressor=make_compressor("qsgd"),
            base_opt=sgd(momentum=0.9), lr_schedule=constant(0.1), fused=True,
        )
    assert "qsgd" in str(ei.value)
    assert "IntSGD" not in str(ei.value)
    # optimizer without a fused kernel form (nesterov): names fused_kernel
    with pytest.raises(ValueError, match="fused_kernel"):
        build_train_step(
            cfg, mesh, shape, compressor=make_compressor("intsgd"),
            base_opt=sgd(momentum=0.9, nesterov=True),
            lr_schedule=constant(0.1), fused=True,
        )
    # the capability survives neither opaque wrapping...
    from repro.optim.base import chain_clip_by_global_norm

    with pytest.raises(ValueError, match="fused_kernel"):
        build_train_step(
            cfg, mesh, shape, compressor=make_compressor("intsgd"),
            base_opt=chain_clip_by_global_norm(sgd(momentum=0.9), 1.0),
            lr_schedule=constant(0.1), fused=True,
        )
    # ...while every capable pair builds: {sgd, adamw} × {intsgd, intdiana}
    for opt in (sgd(momentum=0.9), adamw()):
        for comp in ("intsgd", "intdiana"):
            build_train_step(
                cfg, mesh, shape, compressor=make_compressor(comp),
                base_opt=opt, lr_schedule=constant(0.1), fused=True,
            )


@pytest.mark.parametrize("shape", [(6,), (3, 4), (1, 3, 4), (2, 3, 4), (2, 2, 3, 4)])
@pytest.mark.parametrize("n_dp", [1, 3])
def test_zero1_rows_are_the_leaf_in_row_major_order(shape, n_dp):
    """ZeRO-1's master layout holds every leaf, stacked layers too (they go
    through their 2-D view), as its row-major elements, padded with zeros,
    split into n_dp rows; own_row gives each member its row."""
    from repro.optim.zero1 import own_row, shard_leaf

    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape) + 1.0
    per = -(-x.size // n_dp)
    want = np.pad(np.arange(x.size, dtype=np.float32) + 1.0,
                  (0, per * n_dp - x.size)).reshape(n_dp, per)
    np.testing.assert_array_equal(shard_leaf(x, n_dp), want)
    for i in range(n_dp):
        np.testing.assert_array_equal(jax.jit(own_row, static_argnums=1)(
            x, n_dp, jnp.int32(i)), want[i])
