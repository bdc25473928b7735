"""The system under test, driven as the trainer drives it.

``launch/train.py::train_loop`` builds the step with ``build_train_step``,
the optimizer state with ``build_init_state``, the weights with
``init_lm_params``, feeds ``SyntheticLMData`` batches and reads the loss on
the host every step. ``Program`` does the same, from a configuration file
and a traffic file, and keeps the host spans the trace reduction reads:
``data`` (making and placing the batch), ``step`` (the call) and
``loss_read`` (the host's wait for the loss). Set-up reads each loss at
once, as the trainer does; the window feeds batches made in set-up, sends
steps ahead and reads each loss later (``batches``, ``dispatch``, ``read``).
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time

from bench import ROOT

sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.core import make_compressor  # noqa: E402
from repro.data.synthetic import SyntheticLMData  # noqa: E402
from repro.launch.step import build_init_state, build_train_step  # noqa: E402
from repro.models.transformer import init_lm_params  # noqa: E402
from repro.optim import sgd  # noqa: E402
from repro.optim.schedules import constant, warmup_wrap  # noqa: E402
from repro.parallel.collectives import mesh_from_counts  # noqa: E402


def model_config(c: dict) -> ModelConfig:
    """The program's configuration: every key of the configuration file
    that names a ``ModelConfig`` field, but the provenance string
    ``source``. The file's other keys (``param_dtype``, ``published``,
    ``reduced``, ``assumed``, ``deployment``, ...) stay the benchmark's."""
    fields = {f.name for f in dataclasses.fields(ModelConfig)} - {"source"}
    return ModelConfig(**{k: v for k, v in c.items() if k in fields})


def prng_seed(seed: int) -> int:
    """The seed as JAX's 32-bit PRNG keys hold it."""
    return seed % 2**32


class Program:
    """One cell's compiled step, its state and its data, from one seed."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int):
        t = traffic
        self.cfg = model_config(config)
        self.traffic = t
        self.chips = chips
        self.mesh = mesh_from_counts(data=chips, model=1)
        self.global_batch = t["batch_per_chip"] * chips
        self.seq = t["seq_len"]
        shape = ShapeConfig("bench", self.seq, self.global_batch, "train")
        comp = make_compressor(
            t["compressor"], **({"wire": t["wire"]} if t["wire"] else {}))
        opt = sgd(momentum=t["momentum"], weight_decay=t["weight_decay"])
        fused = t["route"] == "fused"
        self.art = build_train_step(
            self.cfg, self.mesh, shape, compressor=comp, base_opt=opt,
            lr_schedule=warmup_wrap(constant(t["lr"]), t["warmup_steps"]),
            param_dtype=jnp.float32, fused=fused, clip_norm=t["clip_norm"],
            overlap=t["overlap"], microbatches=t["microbatches"],
        )
        self.init_state = build_init_state(
            self.cfg, self.mesh, compressor=comp, base_opt=opt, fused=fused)
        cfg = self.cfg
        self.init_params = jax.jit(
            lambda k: init_lm_params(k, cfg, tp=1, n_shards=1,
                                     dtype=jnp.float32),
            out_shardings=self.art.in_shardings[0],
        )
        self.reseed(seed)
        self.tokens_per_step = self.global_batch * self.seq
        self.params = self.opt_state = self.comp_state = None
        self._norms = jax.jit(lambda t: [
            jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(t)])
        self._diff = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
        self._grad0 = jax.jit(lambda p1, p0, eta, wd: jax.tree.map(
            lambda a, b: (b - a) / eta - wd * b, p1, p0))

    # -- state ---------------------------------------------------------------
    def reseed(self, seed: int):
        """Weights, step keys and data from `seed`; the compiled programs
        stay."""
        self.seed = prng_seed(seed)
        self.key = jax.random.PRNGKey(self.seed)
        self.data = SyntheticLMData(self.cfg.vocab, self.seq,
                                    self.global_batch, seed=self.seed)

    def start(self):
        """Weights from the seed, made on the device, and their state."""
        self.params = self.init_params(self.key)
        self.opt_state, self.comp_state = self.init_state(self.params)
        jax.block_until_ready((self.params, self.opt_state, self.comp_state))

    def free(self):
        self.params = self.opt_state = self.comp_state = None

    def program_for(self, i: int):
        """Step 0 on the trainer's exact float step where the traffic asks
        for it, every other step on the compressed one."""
        which = (self.traffic["step0_program"] if i == 0 else "compressed")
        return self.art.jitted[which]

    # -- one step, as train_loop runs it -----------------------------------
    def batch(self, i: int):
        with jax.profiler.TraceAnnotation("data"):
            sh = self.art.in_shardings[5]
            return {k: jax.device_put(v, sh[k])
                    for k, v in self.data.batch(i, 0).items()}

    def batches(self, first: int, n: int) -> list:
        """Steps first..first+n-1's batches, made and placed at once."""
        out = [self.batch(i) for i in range(first, first + n)]
        jax.block_until_ready(out)
        return out

    def dispatch(self, i: int, batch, fn=None):
        """Sends step i on `batch` to the device; returns its loss unread."""
        fn = fn or self.program_for(i)
        with jax.profiler.TraceAnnotation("step"):
            out = fn(self.params, self.opt_state, self.comp_state,
                     jnp.int32(i), jax.random.fold_in(self.key, i), batch)
            self.params, self.opt_state, self.comp_state, loss, _ = out
        return loss

    @staticmethod
    def read(loss) -> float:
        """The host's read of a step's loss, which waits for the step."""
        with jax.profiler.TraceAnnotation("loss_read"):
            return float(loss)

    def step(self, i: int, batch, fn=None):
        """Step i on `batch`, read at once, as ``train_loop`` runs it;
        returns (loss, seconds from call to loss read)."""
        t0 = time.perf_counter()
        value = self.read(self.dispatch(i, batch, fn))
        return value, time.perf_counter() - t0

    def lowered(self, i: int):
        """Step i's program lowered for the arguments the window passes."""
        return self.program_for(i).lower(
            self.params, self.opt_state, self.comp_state, jnp.int32(i),
            jax.random.fold_in(self.key, i), self.batch(i))

    # -- what the check reads of the program's state -------------------------
    def first_steps(self, n: int) -> dict:
        """Steps 0..n-1 through the window's own call and feed, with the
        check's readings of the program: each step's loss and batch, the
        first gradient's leaf norms and the change's after step n-1."""
        out = {"losses": [], "batches": [], "step_s": []}
        for i in range(n):
            batch = self.batch(i)
            out["batches"].append(tuple(
                jax.device_get(batch[k]) for k in ("tokens", "labels")))
            loss, dt = self.step(i, batch)
            out["losses"].append(loss)
            out["step_s"].append(dt)
            if i == 0:
                out["grad0"] = self.grad0_norms()
        out["change"] = self.change_norms()
        out["leaves"] = self.leaf_names()
        return out

    def leaf_names(self):
        return [jax.tree_util.keystr(p) for p, _ in
                jax.tree_util.tree_flatten_with_path(self.params)[0]]

    def lr(self, i: int) -> float:
        t = self.traffic
        return t["lr"] * min(i + 1, t["warmup_steps"]) / t["warmup_steps"]

    def grad0_norms(self):
        """Leaf norms of the first gradient as the optimizer received it,
        from the weights after step 0: x1 = x0 − η0 (ĝ0 + λ x0)."""
        p0 = self.init_params(self.key)
        g = self._grad0(self.params, p0, jnp.float32(self.lr(0)),
                        jnp.float32(self.traffic["weight_decay"]))
        del p0
        return [float(v) for v in self._norms(g)]

    def change_norms(self):
        """Leaf norms of the weights' change since the start."""
        p0 = self.init_params(self.key)
        d = self._diff(self.params, p0)
        del p0
        return [float(v) for v in self._norms(d)]
