"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bit-exact where
the math is exact, allclose where FMA reassociation applies."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

SHAPES = [(7,), (128,), (1000,), (8, 128), (300, 700), (3, 5, 7), (2, 3, 4, 5)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("bits", [8, 32])
@pytest.mark.parametrize("stochastic", [True, False])
def test_int_compress_matches_oracle(shape, bits, stochastic):
    key = jax.random.PRNGKey(hash((shape, bits)) % 2**31)
    x = jax.random.normal(key, shape, jnp.float32) * 5.0
    alpha = jnp.float32(23.7)
    seed = ops.seed_from_key(key)
    got = ops.int_compress(
        x, alpha, key, n_workers=4, bits=bits, stochastic=stochastic
    )
    want = ref.int_compress_ref(
        x, alpha, seed, n_workers=4, bits=bits, stochastic=stochastic
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int_compress_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (333,), jnp.float32).astype(dtype)
    got = ops.int_compress(x, jnp.float32(100.0), key, n_workers=2)
    want = ref.int_compress_ref(
        x, jnp.float32(100.0), ops.seed_from_key(key), n_workers=2
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int_compress_unbiased_statistics():
    """Kernel's stochastic rounding is unbiased: mean(Int(αx)/α) ≈ mean(x)."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (200_000,))
    alpha = jnp.float32(3.0)
    ints = ops.int_compress(x, alpha, key, n_workers=1)
    err = float(jnp.mean(ints.astype(jnp.float32) / alpha - x))
    assert abs(err) < 1e-3


@pytest.mark.parametrize("shape", [(7,), (128,), (300, 700), (3, 5, 7)])
@pytest.mark.parametrize("bits", [4, 8, 16])
def test_pack_words_matches_oracle(shape, bits):
    """Pallas pack kernel vs the independent uint32-mul oracle, bit-exact."""
    key = jax.random.PRNGKey(hash((shape, bits)) % 2**31)
    lim = ref._INT_LIM[bits] // 4
    ints = jax.random.randint(key, shape, -lim, lim + 1)
    got = ops.pack_words(ints, bits=bits, n_workers=4)
    want = ref.pack_words_ref(ints, bits=bits, n_workers=4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(7,), (1000,), (33, 9)])
@pytest.mark.parametrize("bits", [4, 8])
def test_unpack_words_matches_oracle_after_sum(shape, bits):
    """Unpack kernel inverts a 4-worker wrap-around word sum, bit-exact."""
    n = 4
    key = jax.random.PRNGKey(hash((shape, bits)) % 2**31)
    lim = ref._INT_LIM[bits] // n
    size = int(np.prod(shape))
    ints = jax.random.randint(key, (n, size), -lim, lim + 1)
    wsum = sum(
        ops.pack_words(ints[i].reshape(shape), bits=bits, n_workers=n)
        for i in range(n)
    )
    got = ops.unpack_words(wsum, shape, bits=bits, n_summed=n)
    want = ref.unpack_words_ref(wsum, shape, bits=bits, n_summed=n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.sum(ints, axis=0).reshape(shape))
    )


# (64,) and (513, 300) take the padded view, the others their native rows
FUSED_UNPACK_SHAPES = [(64,), (513, 300), (64, 256), (3, 64, 128),
                       (2, 32, 960)]


@pytest.mark.parametrize("shape", FUSED_UNPACK_SHAPES)
def test_fused_unpack_update_matches_oracle(shape):
    """The packed-wire fused kernel == unpack + fused-update composition."""
    n, bits = 4, 8
    key = jax.random.PRNGKey(11)
    lim = ref._INT_LIM[bits] // n
    size = int(np.prod(shape))
    ints = jax.random.randint(key, (n, size), -lim, lim + 1)
    wsum = sum(
        ops.pack_words(ints[i].reshape(shape), bits=bits, n_workers=n)
        for i in range(n)
    )
    p = jax.random.normal(key, shape)
    m = jax.random.normal(jax.random.fold_in(key, 1), shape)
    got_p, got_m = ops.fused_unpack_update(
        wsum, p, m, 1e-3, 0.1, 0.9, 1e-4, bits=bits, n_summed=n
    )
    want_p, want_m = ref.fused_unpack_update_ref(
        wsum, p, m, bits=bits, n_summed=n,
        inv_nalpha=jnp.float32(1e-3), lr=jnp.float32(0.1),
        mu=jnp.float32(0.9), wd=jnp.float32(1e-4),
    )
    np.testing.assert_allclose(got_p, want_p, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_m, want_m, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shape", [(64,), (513, 300), (4, 4, 4)])
def test_fused_update_matches_oracle(shape):
    key = jax.random.PRNGKey(1)
    ints = jax.random.randint(key, shape, -1000, 1000)
    p = jax.random.normal(key, shape)
    m = jax.random.normal(jax.random.fold_in(key, 1), shape)
    got_p, got_m = ops.fused_update(ints, p, m, 1e-3, 0.1, 0.9, 1e-4)
    want_p, want_m = ref.fused_update_ref(
        ints, p, m,
        inv_nalpha=jnp.float32(1e-3), lr=jnp.float32(0.1),
        mu=jnp.float32(0.9), wd=jnp.float32(1e-4),
    )
    np.testing.assert_allclose(got_p, want_p, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_m, want_m, rtol=1e-5, atol=1e-6)


def _adamw_scalars(*, inv_nalpha, clip, lr, b1, b2, eps, wd, t):
    """Canonical adamw scalar vector (kernels/fused_update.py layout)."""
    return jnp.stack([
        jnp.float32(inv_nalpha), jnp.float32(clip), jnp.float32(lr),
        jnp.float32(b1), jnp.float32(1.0 - b1), jnp.float32(b2),
        jnp.float32(1.0 - b2), jnp.float32(eps), jnp.float32(wd),
        jnp.float32(1.0 - b1**t), jnp.float32(1.0 - b2**t),
    ])


@pytest.mark.parametrize("shape", FUSED_UNPACK_SHAPES)
@pytest.mark.parametrize("with_shift", [False, True])
def test_fused_unpack_adamw_matches_oracle(shape, with_shift):
    """fused_unpack_adamw_2d == unpack + bias-corrected AdamW composition,
    with and without the IntDIANA global shift (whose new value must be the
    UNCLIPPED decoded aggregate)."""
    n, bits, t = 4, 8, 3
    key = jax.random.PRNGKey(13)
    lim = ref._INT_LIM[bits] // n
    size = int(np.prod(shape))
    ints = jax.random.randint(key, (n, size), -lim, lim + 1)
    wsum = sum(
        ops.pack_words(ints[i].reshape(shape), bits=bits, n_workers=n)
        for i in range(n)
    )
    p = jax.random.normal(key, shape)
    mu = jax.random.normal(jax.random.fold_in(key, 1), shape) * 0.1
    nu = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), shape)) * 0.01
    h = (jax.random.normal(jax.random.fold_in(key, 3), shape) * 0.3
         if with_shift else None)
    kw = dict(inv_nalpha=1e-3, lr=0.05, b1=0.9, b2=0.95, eps=1e-8, wd=0.1)
    sc = _adamw_scalars(clip=0.7, t=t, **kw)
    got_p, (got_m, got_v), got_h = ops.fused_unpack_apply(
        wsum, p, (mu, nu), sc, h, kernel="adamw", bits=bits, n_summed=n
    )
    want_p, want_m, want_v, want_h = ref.fused_unpack_adamw_ref(
        wsum, p, mu, nu, bits=bits, n_summed=n,
        clip=jnp.float32(0.7), shift=h,
        bc1=jnp.float32(1.0 - 0.9**t), bc2=jnp.float32(1.0 - 0.95**t),
        **{k: jnp.float32(v) for k, v in kw.items()},
    )
    np.testing.assert_allclose(got_p, want_p, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_m, want_m, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-5, atol=1e-7)
    if with_shift:
        np.testing.assert_allclose(got_h, want_h, rtol=1e-5, atol=1e-6)
    else:
        assert got_h is None


@pytest.mark.parametrize("shape", [(64, 256), (3, 64, 128), (2, 32, 960)])
@pytest.mark.parametrize("kernel,with_shift",
                         [("sgd", False), ("sgd", True), ("adamw", True)])
def test_fused_unpack_views_bit_identical(shape, kernel, with_shift):
    """The native row-chunk view and the padded image view of one leaf give
    the same p', state' and shift', bit for bit. Inputs and scalars are
    short dyadic numbers, so every product is exact and no rounding depends
    on whether the compiler contracts a multiply and add into an FMA (the
    CPU's XLA does so differently in each fusion); what is compared is
    which element meets which word field."""
    n, bits = 4, 8
    n_tensors = 2 + (kernel == "adamw") + with_shift
    assert ops.fused_view(shape, bits=bits, n_tensors=n_tensors) == "native"
    key = jax.random.PRNGKey(17)
    lim = ref._INT_LIM[bits] // n
    ints = jax.random.randint(key, (n, *shape), -lim, lim + 1)
    wsum = sum(ops.pack_words(ints[i], bits=bits, n_workers=n)
               for i in range(n))
    dyadic = lambda i, lo, hi, scale: jax.random.randint(
        jax.random.fold_in(key, i), shape, lo, hi).astype(jnp.float32) / scale
    p = dyadic(0, -64, 64, 16)
    opt = (dyadic(1, -64, 64, 16),)
    if kernel == "adamw":
        opt += (dyadic(2, 0, 64, 64),)
        # inv_nalpha, clip, lr, b1, 1-b1, b2, 1-b2, eps, wd, bc1, bc2
        sc = [2**-10, 0.5, 0.25, 0.5, 0.5, 0.75, 0.25, 2**-10, 2**-6,
              0.5, 0.25]
    else:  # inv_nalpha, clip, lr, mu, wd
        sc = [2**-10, 0.5, 0.25, 0.5, 2**-6]
    sc = jnp.array(sc, jnp.float32)
    h = dyadic(3, -64, 64, 16) if with_shift else None
    kw = dict(kernel=kernel, bits=bits, n_summed=n)
    native = ops.fused_unpack_apply(wsum, p, opt, sc, h, **kw)
    padded = ops._fused_unpack_in(ops._padded_views, wsum, p, opt, sc, h,
                                  **kw)
    for got, want in zip(jax.tree.leaves(native), jax.tree.leaves(padded),
                         strict=True):
        assert got.shape == shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_view_by_shape():
    """A 1-D leaf, rows not a multiple of k and rows/k not a multiple of 8
    take the padded view; whole row chunks of 8k rows the native one, at
    any width that a block fits."""
    view = lambda shape, bits=8: ops.fused_view(shape, bits=bits)
    assert view((4096,)) == "padded"
    assert view((30, 128)) == "padded"  # rows % 4
    assert view((3, 36, 128)) == "padded"  # rows/k = 27
    assert view((32, 128)) == "native"
    assert view((2, 3840, 960)) == "native"  # C not a multiple of 128
    assert view((64, 128), bits=4) == "native"  # k = 8: rows/k = 8
    assert view((32, 128), bits=4) == "padded"  # rows/k = 4
    assert view((16, 2**20)) == "padded"  # no block of 8 rows fits


def test_fused_view_line_at_danube_widths():
    """h2o-danube-3-4b at its published widths and 2 layers, as the chip
    benchmark runs it: every leaf but the three norms takes its native
    view, on the SGD kernel with or without a shift, and on AdamW's."""
    import json
    import pathlib

    from repro.configs.base import ModelConfig
    from repro.launch.train import fused_view_line
    from repro.models.transformer import init_lm_params

    path = (pathlib.Path(__file__).parents[1]
            / "benchmarks/chip/configs/h2o-danube-3-4b.json")
    c = json.loads(path.read_text())
    cfg = ModelConfig(**{k: c[k] for k in (
        "name", "family", "n_layers", "d_model", "n_heads", "n_kv_heads",
        "d_ff", "vocab", "head_dim", "window", "rope_theta",
        "tie_embeddings", "qkv_bias")})
    params = jax.eval_shape(
        lambda k: init_lm_params(k, cfg, tp=1, n_shards=1,
                                 dtype=jnp.float32), jax.random.PRNGKey(0))
    shapes = [x.shape for x in jax.tree.leaves(params)]
    assert len(shapes) == 12
    for n_tensors in (2, 3, 4):
        assert fused_view_line(shapes, bits=8, n_tensors=n_tensors) == (
            "[train] fused view: 555417600 of 555436800 in place, "
            "3 leaves padded")


@given(st.integers(1, 3000), st.integers(1, 60), st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_fused_adamw_kernel_matches_optimizer_update(size, t, seed):
    """Property: the fused AdamW kernel reproduces the REFERENCE optimizer
    (optim/adamw.py::update — the exact arithmetic the unfused ZeRO-1 route
    runs) on random integer images, for any size and step count."""
    from repro.optim import adamw

    key = jax.random.PRNGKey(seed)
    ints = jax.random.randint(key, (size,), -4 * 127, 4 * 127 + 1)
    p = jax.random.normal(jax.random.fold_in(key, 1), (size,))
    mu = jax.random.normal(jax.random.fold_in(key, 2), (size,)) * 0.1
    nu = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (size,))) * 0.01
    inv_nalpha, lr = 2.5e-3, 0.07
    opt = adamw()  # b1=0.9, b2=0.95, eps=1e-8, wd=0.1
    h = opt.hyper
    state = {"mu": {"w": mu}, "nu": {"w": nu},
             "count": jnp.asarray(t - 1, jnp.int32)}
    g = {"w": ints.astype(jnp.float32) * inv_nalpha}
    upd, st2 = opt.update(g, state, {"w": p}, jnp.float32(lr))
    want_p = p + upd["w"]
    sc = _adamw_scalars(
        inv_nalpha=inv_nalpha, clip=1.0, lr=lr, b1=h["b1"], b2=h["b2"],
        eps=h["eps"], wd=h["weight_decay"], t=t,
    )
    got_p, (got_m, got_v), _ = ops.fused_apply(
        ints, p, (mu, nu), sc, kernel="adamw"
    )
    np.testing.assert_allclose(got_p, want_p, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_m, st2["mu"]["w"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_v, st2["nu"]["w"], rtol=1e-5, atol=1e-7)


def test_fused_sgd_shift_emits_decoded_aggregate():
    """SGD kernel with the IntDIANA shift: new shift == h + Σints·inv_nα
    (unclipped), while the update consumes clip·(h + Σints·inv_nα)."""
    key = jax.random.PRNGKey(5)
    ints = jax.random.randint(key, (1000,), -500, 500)
    p = jax.random.normal(key, (1000,))
    m = jax.random.normal(jax.random.fold_in(key, 1), (1000,))
    h = jax.random.normal(jax.random.fold_in(key, 2), (1000,)) * 0.2
    inv_nalpha, clip, lr, mu, wd = 2e-3, 0.6, 0.05, 0.9, 1e-4
    sc = jnp.stack([jnp.float32(x) for x in (inv_nalpha, clip, lr, mu, wd)])
    got_p, (got_m,), got_h = ops.fused_apply(
        ints, p, (m,), sc, h, kernel="sgd"
    )
    g_agg = ints * inv_nalpha + h
    m2 = mu * m + (clip * g_agg + wd * p)
    np.testing.assert_allclose(got_h, g_agg, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_m, m2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_p, p - lr * m2, rtol=1e-5, atol=1e-6)


def test_fused_update_equals_sgd_semantics():
    """Fused kernel == decode + torch-SGD reference sequence."""
    key = jax.random.PRNGKey(2)
    ints = jax.random.randint(key, (1000,), -500, 500)
    p = jax.random.normal(key, (1000,))
    m = jnp.zeros((1000,))
    inv_nalpha, lr, mu, wd = 2e-3, 0.05, 0.9, 1e-4
    got_p, got_m = ops.fused_update(ints, p, m, inv_nalpha, lr, mu, wd)
    g = ints * inv_nalpha + wd * p
    m2 = mu * m + g
    p2 = p - lr * m2
    np.testing.assert_allclose(got_p, p2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_m, m2, rtol=1e-5, atol=1e-6)


@given(st.integers(1, 5000), st.integers(1, 8))
@settings(max_examples=20, deadline=None)
def test_block_norms_property(size, nblocks):
    """Sum of block norms == total ||x||² for any size/block split."""
    x = jax.random.normal(jax.random.PRNGKey(size), (size,))
    bn = ops.block_sq_norms(x, nblocks)
    assert bn.shape == (nblocks,)
    np.testing.assert_allclose(
        float(jnp.sum(bn)), float(jnp.sum(x * x)), rtol=1e-4
    )


def test_sq_norm_kernel():
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 130))
    np.testing.assert_allclose(
        float(ops.sq_norm(x)), float(jnp.sum(x * x)), rtol=1e-5
    )
