"""Plain reference of a training cell: the model, its loss and gradients,
IntSGD's encode, integer sum and decode, the global-norm clip and momentum
SGD, in straightforward ``jax.numpy`` at float32.

It imports nothing of the program under test. It makes its own weights from
the seed by the recipe the configuration's model uses (uniform in
±1/sqrt(fan_in), ones for the norm weights, one PRNG key per tensor split in
the model's order), and runs the same data-parallel algorithm worker by
worker on one device:

  step 0      exact: the mean of the workers' float gradients;
  step k > 0  IntSGD (Alg. 1): α = sqrt(d) / sqrt(2 n r / η² + ε²),
              each worker sends clip(Int(α g_i), ±lim), lim = (2^(b-1)-1)//n,
              and ĝ = Σ_i Int(α g_i) / (n α);
  then        ĝ ← ĝ · min(1, c / ||ĝ||); m ← μ m + ĝ + λ x; x ← x − η m;
              r ← β r + (1 − β) ||(1 − μ)(x' − x)||².

Its stochastic rounding draws from a hash of its own, so its rounding noise
is independent of the program's: the comparison reads norms, which the
noise moves by far less than the limits.

Matrix products go through one function of ``MATMULS``: float32 at the
``highest`` precision for the reference, or operands rounded to float8
(e4m3, one scale per tensor) for the control, in the backward pass too.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512  # attention rows computed together; bounds the score tile
NEG_INF = -1e30
F8_MAX = 448.0  # largest finite float8_e4m3fn


# ---------------------------------------------------------------------------
# matrix products: the reference's and the control's
# ---------------------------------------------------------------------------
def _mm_f32(a, b):
    return jnp.einsum("...i,io->...o", a, b, precision=HIGHEST)


def _to_f8(x):
    """x rounded to float8 e4m3 with one scale for the tensor, back in f32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _mm_f8(a, b):
    return _mm_f32(_to_f8(a), _to_f8(b))


def _mm_f8_fwd(a, b):
    qa, qb = _to_f8(a), _to_f8(b)
    return _mm_f32(qa, qb), (qa, qb)


def _mm_f8_bwd(res, ct):
    qa, qb = res
    qct = _to_f8(ct)
    da = jnp.einsum("...o,io->...i", qct, qb, precision=HIGHEST)
    db = jnp.einsum("...i,...o->io", qa, qct, precision=HIGHEST)
    return da, db


_mm_f8.defvjp(_mm_f8_fwd, _mm_f8_bwd)

MATMULS = {"float32": _mm_f32, "float8": _mm_f8}


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------
def _uniform(key, shape, fan_in):
    s = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -s, s)


def _layer(key, c):
    d, f = c["d_model"], c["d_ff"]
    q, kv = c["n_heads"] * c["head_dim"], c["n_kv_heads"] * c["head_dim"]
    k_attn, k_mlp = jax.random.split(key, 2)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "ln2": jnp.ones((d,), jnp.float32),
        "attn": {
            "wq": _uniform(ka[0], (d, q), d),
            "wk": _uniform(ka[1], (d, kv), d),
            "wv": _uniform(ka[2], (d, kv), d),
            "wo": _uniform(ka[3], (q, d), q),
        },
        "mlp": {
            "w_gate": _uniform(km[0], (d, f), d),
            "w_up": _uniform(km[1], (d, f), d),
            "w_down": _uniform(km[2], (f, d), f),
        },
    }


def init_params(c, key):
    """The configuration's weights from a PRNG key, layers stacked."""
    d, v = c["d_model"], c["vocab"]
    keys = jax.random.split(key, 8)
    p = {
        "embed": _uniform(keys[0], (v, d), d),
        "ln_f": jnp.ones((d,), jnp.float32),
    }
    if not c["tie_embeddings"]:
        p["lm_head"] = _uniform(keys[1], (d, v), d)
    layers = [_layer(k, c) for k in jax.random.split(keys[2], c["n_layers"])]
    p["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return p


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _rmsnorm(x, w, eps=1e-6):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate the two halves of each head (x: B, T, H, dh; pos: T)."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(p, x, c, mm):
    b, t, _ = x.shape
    nq, nkv, dh = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    group = nq // nkv
    pos = jnp.arange(t, dtype=jnp.int32)
    q = _rope(mm(x, p["wq"]).reshape(b, t, nq, dh), pos, c["rope_theta"])
    k = _rope(mm(x, p["wk"]).reshape(b, t, nkv, dh), pos, c["rope_theta"])
    v = mm(x, p["wv"]).reshape(b, t, nkv, dh)
    # query head h reads key/value head h // group
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    window = c.get("window")

    @jax.checkpoint
    def rows(qb, qpos):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST)
        s = s / math.sqrt(dh)
        ok = pos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= pos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)

    blk = min(QUERY_BLOCK, t)
    out = jnp.concatenate(
        [rows(q[:, i:i + blk], pos[i:i + blk]) for i in range(0, t, blk)],
        axis=1,
    )
    return mm(out.reshape(b, t, nq * dh), p["wo"])


def _mlp(p, x, mm):
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])


def loss_fn(params, tokens, labels, c, mm):
    """Mean next-token cross entropy over the positions whose label >= 0."""
    x = params["embed"][tokens]
    for i in range(c["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])

        @jax.checkpoint
        def layer(x, lp):
            h = x + _attention(lp["attn"], _rmsnorm(x, lp["ln1"]), c, mm)
            return h + _mlp(lp["mlp"], _rmsnorm(h, lp["ln2"]), mm)

        x = layer(x, lp)
    h = _rmsnorm(x, params["ln_f"])
    head = params["embed"].T if c["tie_embeddings"] else params["lm_head"]
    logits = mm(h, head)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.clip(labels, 0, None)[..., None], axis=-1
    )[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# IntSGD's integer image, with the reference's own rounding noise
# ---------------------------------------------------------------------------
def _hash32(x):
    """lowbias32 (C. Wellons): an integer hash unrelated to the program's."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _uniforms(shape, seed):
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in reversed(range(len(shape))):
        idx = idx + lax.broadcasted_iota(jnp.uint32, shape, ax) * np.uint32(stride)
        stride *= int(shape[ax])
    h = _hash32(_hash32(idx ^ seed.astype(jnp.uint32)) + np.uint32(0x9E3779B9))
    return (h >> np.uint32(8)).astype(jnp.float32) * np.float32(2.0**-24)


def _int_image(g, alpha, seed, lim):
    t = g * alpha
    lo = jnp.floor(t)
    r = lo + (_uniforms(g.shape, seed) < t - lo).astype(jnp.float32)
    return jnp.clip(r, -lim, lim).astype(jnp.int32)


# ---------------------------------------------------------------------------
# the training steps
# ---------------------------------------------------------------------------
def _leaf_sq(tree):
    return [jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)]


class Trainer:
    """The reference's jitted pieces for one configuration and job."""

    def __init__(self, c, job, *, precision="float32"):
        self.c, self.job = c, job
        mm = MATMULS[precision]
        grad = jax.value_and_grad(partial(loss_fn, c=c, mm=mm))
        self.grad = jax.jit(grad)
        self.init = jax.jit(partial(init_params, c))
        self.add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                           donate_argnums=0)
        self.encode = jax.jit(self._encode, donate_argnums=0)
        self.update = jax.jit(self._update, donate_argnums=(0, 1))
        self.norms = jax.jit(lambda t: [jnp.sqrt(s) for s in _leaf_sq(t)])
        wd = job["weight_decay"]
        self.grad0 = jax.jit(
            lambda m, x0: jax.tree.map(lambda m, p: m - wd * p, m, x0))
        self.delta = jax.jit(
            lambda x, x0: jax.tree.map(jnp.subtract, x, x0))

    @staticmethod
    def _encode(acc, g, alpha, seeds, lim):
        leaves, tdef = jax.tree.flatten(g)
        ints = [_int_image(x, alpha, seeds[i], lim) for i, x in enumerate(leaves)]
        return jax.tree.map(jnp.add, acc, jax.tree.unflatten(tdef, ints))

    def _update(self, x, m, ghat, eta, r):
        j = self.job
        sq = sum(_leaf_sq(ghat))
        scale = jnp.minimum(1.0, j["clip_norm"] / (jnp.sqrt(sq) + 1e-12))
        mu, wd = j["momentum"], j["weight_decay"]
        m = jax.tree.map(lambda m, g, p: mu * m + g * scale + wd * p, m, ghat, x)
        new = jax.tree.map(lambda p, m: p - eta * m, x, m)
        dx = sum(_leaf_sq(jax.tree.map(lambda a, b: a - b, new, x)))
        r = 0.9 * r + 0.1 * (1.0 - mu) ** 2 * dx
        return new, m, r

    def lr(self, k):
        j = self.job
        return j["lr"] * min(k + 1, j["warmup_steps"]) / j["warmup_steps"]


def run(trainer, key, batches, *, n_workers, compressed, noise_seed=0,
        fault=None):
    """The reference's first len(batches) steps from the weights of `key`.

    ``batches[k]`` is step k's global (tokens, labels) as numpy arrays, rows
    split evenly over the n workers. ``compressed(k)`` says whether step k
    is an IntSGD step; ``noise_seed`` keys the rounding noise. ``fault``
    plants the fault of a one-chip cell that the benchmark's check has to
    catch: "half_batch" (half of each row's tokens left out, the mean taken
    over the rest).

    Returns the per-step losses (the workers' mean, as the program reports
    it), the leaf norms of the first gradient as the optimizer receives it
    (after the clip), and the leaf norms of the change after all steps.
    The starting weights are made again from `key` where they are needed
    rather than kept, so that the reference fits beside its state."""
    j = trainer.job
    x = trainer.init(key)
    m = jax.tree.map(jnp.zeros_like, x)
    r = jnp.zeros((), jnp.float32)
    d = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(x))
    lim = (2 ** (j["bits"] - 1) - 1) // n_workers if j["bits"] else 0
    losses, grad0 = [], None
    for k, (tokens, labels) in enumerate(batches):
        labels = np.array(labels)
        if fault == "half_batch":
            labels[:, labels.shape[1] // 2:] = -1
        rows = np.split(np.arange(tokens.shape[0]), n_workers)
        eta = jnp.float32(trainer.lr(k))
        alpha = None
        if compressed(k):
            alpha = jnp.sqrt(jnp.float32(d)) / jnp.sqrt(
                2.0 * n_workers * r / jnp.square(eta) + 1e-16
            )
        acc, loss = None, 0.0
        for w in range(n_workers):
            lw, g = trainer.grad(x, jnp.asarray(tokens[rows[w]]),
                                 jnp.asarray(labels[rows[w]]))
            loss += float(lw)
            if alpha is None:
                acc = g if acc is None else trainer.add(acc, g)
            else:
                if acc is None:
                    acc = jax.tree.map(
                        lambda a: jnp.zeros(a.shape, jnp.int32), g)
                seeds = _step_seeds(noise_seed, k, w, len(jax.tree.leaves(g)))
                acc = trainer.encode(acc, g, alpha, seeds, lim)
            del g
        if alpha is None:
            ghat = jax.tree.map(lambda a: a / n_workers, acc)
        else:
            ghat = jax.tree.map(
                lambda s: s.astype(jnp.float32) * (1.0 / (n_workers * alpha)),
                acc)
        del acc
        losses.append(loss / n_workers)
        x, m, r = trainer.update(x, m, ghat, eta, r)
        del ghat
        if k == 0:
            # the optimizer's state after one step is m = ĝ0 + λ x0
            grad0 = trainer.norms(trainer.grad0(m, trainer.init(key)))
    change = trainer.norms(trainer.delta(x, trainer.init(key)))
    return {
        "leaves": leaf_names(x),
        "losses": losses,
        "grad0": [float(v) for v in grad0],
        "change": [float(v) for v in change],
    }


def leaf_names(tree):
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _step_seeds(noise_seed, step, worker, n_leaves):
    rng = np.random.default_rng([noise_seed % 2**63, step, worker])
    return jnp.asarray(rng.integers(0, 2**32, n_leaves, dtype=np.uint64)
                       .astype(np.uint32))
