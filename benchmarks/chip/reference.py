"""Plain reference of a training cell: the model, its loss and gradients,
IntSGD's encode, integer sum and decode, the global-norm clip and momentum
SGD, in straightforward ``jax.numpy`` at float32.

It imports nothing of the program under test. The model is the
configuration's family's (``families/<family>.py``, by the ``family`` key):
its own weights from the seed (``init_params``) and its loss
(``loss_fn``). This file holds what every family shares, and runs the same
data-parallel algorithm worker by worker on one device:

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

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import bench

HIGHEST = lax.Precision.HIGHEST
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
        model = bench.family(c["family"])
        mm = MATMULS[precision]
        grad = jax.value_and_grad(partial(model.loss_fn, c=c, mm=mm))
        self.grad = jax.jit(grad)
        self.init = jax.jit(partial(model.init_params, c))
        self.add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                           donate_argnums=0)
        self.encode = jax.jit(self._encode, donate_argnums=0)
        self.own_share = jax.jit(self._own_share, donate_argnums=0,
                                 static_argnums=(5, 6))
        self.update = jax.jit(self._update, donate_argnums=(0, 1),
                              static_argnames="clip")
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

    def _clip_scale(self, ghat):
        sq = sum(_leaf_sq(ghat))
        return jnp.minimum(1.0, self.job["clip_norm"] / (jnp.sqrt(sq) + 1e-12))

    def _own_share(self, acc, g, alpha, seeds, lim, w, n):
        """acc plus worker w's gradient decoded from its own integers alone
        and clipped by its own norm, on the rows of each leaf that worker w
        updates under ZeRO-1 (the flat leaf padded to n equal rows)."""
        zero = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.int32), g)
        ghat = jax.tree.map(lambda s: s.astype(jnp.float32) * (1.0 / (n * alpha)),
                            self._encode(zero, g, alpha, seeds, lim))
        scale = self._clip_scale(ghat)

        def rows(a, x):
            per = -(-x.size // n)
            i = lax.broadcasted_iota(jnp.int32, (x.size,), 0)
            mine = (i >= w * per) & (i < (w + 1) * per)
            return a + jnp.where(mine, x.reshape(-1) * scale, 0.0).reshape(x.shape)

        return jax.tree.map(rows, acc, ghat)

    def _update(self, x, m, ghat, eta, r, clip=True):
        j = self.job
        scale = self._clip_scale(ghat) if clip else 1.0
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
    plants a fault that the benchmark's check has to catch: "half_batch"
    (half of each row's tokens left out, the mean taken over the rest), or
    "no_exchange" (the workers' integers never summed: on each IntSGD step
    each worker decodes only its own, clips that by its own norm and
    updates with it the ZeRO-1 rows it owns, which the all-gather then
    puts together).

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
        own = alpha is not None and fault == "no_exchange"
        acc, loss = None, 0.0
        for w in range(n_workers):
            lw, g = trainer.grad(x, jnp.asarray(tokens[rows[w]]),
                                 jnp.asarray(labels[rows[w]]))
            loss += float(lw)
            if alpha is None:
                acc = g if acc is None else trainer.add(acc, g)
            else:
                if acc is None:
                    dtype = jnp.float32 if own else jnp.int32
                    acc = jax.tree.map(lambda a: jnp.zeros(a.shape, dtype), g)
                seeds = _step_seeds(noise_seed, k, w, len(jax.tree.leaves(g)))
                if own:
                    acc = trainer.own_share(acc, g, alpha, seeds, lim, w,
                                            n_workers)
                else:
                    acc = trainer.encode(acc, g, alpha, seeds, lim)
            del g
        if alpha is None:
            ghat = jax.tree.map(lambda a: a / n_workers, acc)
        elif own:
            ghat = acc
        else:
            ghat = jax.tree.map(
                lambda s: s.astype(jnp.float32) * (1.0 / (n_workers * alpha)),
                acc)
        del acc
        losses.append(loss / n_workers)
        x, m, r = trainer.update(x, m, ghat, eta, r, clip=not own)
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
