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


@pytest.mark.parametrize("shape", [(8, 4), (2, 8, 4), (2, 2, 8, 4)])
@pytest.mark.parametrize("n_dp", [1, 2])
def test_zero1_native_blocks_hold_the_flat_rows(shape, n_dp):
    """Where n_dp divides a leaf's rows, member j's block of the native
    layout holds, in row-major order, exactly the elements of flat row j;
    the native master layout is the leaf's 2-D view split into n_dp
    blocks."""
    from repro.optim import sgd
    from repro.optim.zero1 import (
        own_block, own_row, shard_leaf, zero1_init, zero1_view)

    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape) + 1.0
    flat = np.asarray(shard_leaf(x, n_dp))
    for j in range(n_dp):
        block = jax.jit(own_block, static_argnums=1)(x, n_dp, jnp.int32(j))
        assert block.shape == (np.prod(shape[:-1]) // n_dp, shape[-1])
        np.testing.assert_array_equal(np.asarray(block).reshape(-1), flat[j])
        np.testing.assert_array_equal(
            np.asarray(block).reshape(-1),
            jax.jit(own_row, static_argnums=1)(x, n_dp, jnp.int32(j)))
    if zero1_view(shape, n_dp, 1) == "native":  # not (8, 4) over 2
        masters = zero1_init(sgd(momentum=0.9), {"w": x}, n_dp, 1)["master"]
        np.testing.assert_array_equal(
            np.asarray(masters["w"]).reshape(n_dp, -1), flat)


def test_zero1_view_by_shape():
    """A 1-D leaf, rows n_dp does not divide, blocks that are not whole
    (8, 128) tiles and tp > 1 take the flat layout; every matrix of
    granite-8b, stacked or not, the native one at one member and at four,
    and every leaf of DeepSeek-V2-Lite with two axes or more at one."""
    from repro.optim.zero1 import zero1_view

    assert zero1_view((4096,), 1, 1) == "flat"
    assert zero1_view((3, 128), 2, 1) == "flat"  # rows % n_dp
    assert zero1_view((24, 128), 2, 1) == "flat"  # blocks of 12 rows
    assert zero1_view((16, 64), 2, 1) == "flat"  # 64 of 128 lanes
    assert zero1_view((16, 192), 2, 1) == "flat"
    assert zero1_view((3, 4), 1, 1) == "native"  # one member: the leaf
    assert zero1_view((16, 128), 2, 1) == "native"
    assert zero1_view((2, 8, 256), 2, 1) == "native"
    assert zero1_view((4096, 14336), 1, 2) == "flat"  # tp > 1
    assert zero1_view((1, 4096), 4, 1) == "flat"  # a stacked norm scale
    assert zero1_view((1, 4096), 1, 1) == "native"
    granite = [(49152, 4096), (1, 4096, 1024), (1, 4096, 4096),
               (1, 14336, 4096), (1, 4096, 14336), (4096, 49152)]
    deepseek = [(5, 8, 2048, 1408), (5, 8, 1408, 2048), (5, 2048, 64),
                (1, 512, 2048), (5, 2816, 2048), (12800, 2048), (5, 2048)]
    for shape in granite:
        for n_dp in (1, 4):
            assert zero1_view(shape, n_dp, 1) == "native", (shape, n_dp)
    for shape in deepseek:
        assert zero1_view(shape, 1, 1) == "native", shape


def _dyadic(shape, seed, scale):
    """Small multiples of `scale` (a power of two): every sum and product
    of the update below is exact, so no contraction of a multiply-add in a
    fusion can round the two layouts apart."""
    r = np.random.default_rng(seed).integers(-64, 65, size=shape)
    return jnp.asarray(r * scale, jnp.float32)


@pytest.mark.parametrize("opt", ["sgd", "adamw"])
@pytest.mark.parametrize("n_dp", [1, 2])
def test_zero1_native_update_matches_flat_bit_for_bit(opt, n_dp):
    """Two zero1_update steps of every dp member (vmapped over the dp axis)
    give the same parameters, and the same master and optimizer state
    element for element, on the native layout as on the flat one (the
    layout tp > 1 takes)."""
    from repro.optim.zero1 import own_share, zero1_init, zero1_update

    base = (sgd(momentum=0.5, weight_decay=0.25) if opt == "sgd" else
            adamw(b1=0.5, b2=0.75, eps=2.0**-10, weight_decay=0.25))
    shapes = {"w": (2, 16, 128), "m": (32, 128), "b": (16,)}
    params = {k: _dyadic(s, i, 2.0**-3) for i, (k, s) in enumerate(
        sorted(shapes.items()))}
    grads = [{k: _dyadic(s, 10 * t + i, 2.0**-4) for i, (k, s) in enumerate(
        sorted(shapes.items()))} for t in (1, 2)]

    def run(tp):
        def member(j):
            own = jax.tree.map(lambda p: own_share(p, n_dp, j, tp), params)
            restack = lambda t: jax.tree.map(
                lambda x: x[None] if x.ndim >= 1 else x, t)
            state = {"master": restack(own), "base": restack(base.init(own))}
            p = params
            for g in grads:
                p, state = zero1_update(
                    base, state, g, jnp.float32(0.25), dp_axes=("dp",),
                    dp_index=j, n_dp=n_dp, tp=tp, param_dtype=jnp.float32,
                    params_like=p)
            return own, p, state

        return jax.jit(jax.vmap(member, axis_name="dp"))(jnp.arange(n_dp))

    (own_n, p_n, s_n), (own_f, p_f, s_f) = run(1), run(2)
    assert s_n["master"]["w"].shape == (n_dp, 1, 32 // n_dp, 128)
    assert s_f["master"]["w"].shape == (n_dp, 1, 4096 // n_dp)
    # each member's share is its block (row) of the global layout
    for own, tp in ((own_n, 1), (own_f, 2)):
        masters = zero1_init(base, params, n_dp, tp)["master"]
        for a, b in zip(jax.tree.leaves(own), jax.tree.leaves(masters)):
            np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(p_n), jax.tree.leaves(p_f)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(s_n), jax.tree.leaves(s_f)):
        np.testing.assert_array_equal(
            np.asarray(a).reshape(n_dp, -1), np.asarray(b).reshape(n_dp, -1))


@pytest.mark.parametrize("n_dp,flat", [(1, 1), (4, 3)])
def test_zero1_view_line_at_granite_widths(n_dp, flat):
    """granite-8b at its published widths and 1 layer, as the chip
    benchmark runs it: every leaf but the final norm scale is held as
    blocks of its own rows on one chip; over four, the layer's two norm
    scales (one row each) stay flat too."""
    import json
    import pathlib

    from repro.configs.base import ModelConfig
    from repro.launch.train import zero1_view_line

    path = (pathlib.Path(__file__).parents[1]
            / "benchmarks/chip/configs/granite-8b.json")
    c = json.loads(path.read_text())
    cfg = ModelConfig(**{k: c[k] for k in (
        "name", "family", "n_layers", "d_model", "n_heads", "n_kv_heads",
        "d_ff", "vocab", "head_dim", "rope_theta", "tie_embeddings")})
    params = jax.eval_shape(
        lambda k: init_lm_params(k, cfg, tp=1, n_shards=1,
                                 dtype=jnp.float32), jax.random.PRNGKey(0))
    shapes = [x.shape for x in jax.tree.leaves(params)]
    assert len(shapes) == 12
    total = 620769280
    assert zero1_view_line(shapes, n_dp=n_dp, tp=1) == (
        f"[train] zero1 view: {total - flat * 4096} of {total} in place, "
        f"{flat} leaves flat")
