"""The check that decides `correct`, on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

The limits of ``tiny.LIMITS`` were set at this size on the CPU by
``calibrate.py``, by the rule each cell's limits follow at the cell's own
size on the chip. A sound run passes them; the
control (the reference in float8, in the program's place) and each fault a
training cell can have, planted under the timed path, do not.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, HERE]

import bench  # noqa: E402
import check  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402
from program import Program, model_config  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2147483693  # above 2**31: a seed JAX keys hold in their 32 bits


def _run(cell, hook=None, seed=SEED):
    return run.run_cell(cell, seed, 0.5, False, device=CPU, program_hook=hook)


def test_reference_weights_are_the_programs():
    from repro.models.transformer import init_lm_params

    key = jax.random.PRNGKey(7)
    ours = bench.family("dense").init_params(tiny.CONFIG, key)
    theirs = init_lm_params(key, model_config(tiny.CONFIG), dtype=jnp.float32)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 ours, theirs)


def test_reference_loss_is_the_models_at_float32():
    from repro.models.common import Axes
    from repro.models.transformer import lm_loss

    cfg = model_config(tiny.CONFIG)
    dense = bench.family("dense")
    params = dense.init_params(tiny.CONFIG, jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 256)
    labels = jnp.roll(toks, -1, axis=1).at[:, -1].set(-1)
    with jax.default_matmul_precision("highest"):
        want = lm_loss(params, {"tokens": toks, "labels": labels}, Axes(),
                       cfg, dtype=jnp.float32)
        got = dense.loss_fn(params, toks, labels, tiny.CONFIG,
                            reference.MATMULS["float32"])
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)


def test_sound_run_is_correct():
    r = _run(tiny.cell())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r["checks"]) == ["loss", "grad0", "change", "change_median",
                                 "window_compiles"]


def test_a_null_limit_is_printed_and_not_compared():
    cell = tiny.cell()
    cell = type(cell)(**dict(cell.__dict__, limits=dict(cell.limits, loss=None)))
    judged = check.judge(cell, {"loss": 1.0, "grad0": 1.0})
    assert judged["loss"] == {"value": 1.0, "limit": None, "ok": True}
    assert not judged["grad0"]["ok"]


class _Queue:
    """A program whose steps are numbered and read back in order."""

    def __init__(self):
        self.sent, self.read_back = [], []

    def dispatch(self, i, batch):
        self.sent.append((i, batch))
        return i

    def read(self, loss):
        self.read_back.append(loss)
        return float(loss)


def test_the_window_reads_every_step_it_sends_in_order():
    q = _Queue()
    w = run.window(q, ["b0", "b1"], 3, 0.05, 4)
    assert [i for i, _ in q.sent] == q.read_back == list(range(3, 3 + len(q.sent)))
    assert [b for _, b in q.sent[:3]] == ["b0", "b1", "b0"]
    assert len(w["done"]) == len(w["losses"]) == len(q.sent) >= 5
    assert w["profiled"] is None and w["done"] == sorted(w["done"])


def test_control_fails_the_limits():
    """The control (the reference's products in float8, one precision
    below the program's bfloat16) in the program's place, on three seeds."""
    cell = tiny.cell()
    prog = Program(cell.config, cell.traffic, 1, 0)
    for seed in (11, 12, 13):
        prog.reseed(seed)
        prog.start()
        batches = prog.first_steps(3)["batches"]
        prog.free()
        ref = check.reference_readings(cell, seed, batches)
        ctl = check.reference_readings(cell, seed, batches,
                                       precision="float8")
        judged = check.judge(cell, check.numbers(ctl, ref))
        assert not all(c["ok"] for c in judged.values()), judged


def _unchanged(prog):
    def wrap(fn):
        def step(p, o, c, *rest):
            kept = jax.tree.map(jnp.copy, (p, o, c))  # the step donates
            out = fn(p, o, c, *rest)
            return kept + tuple(out[3:])
        return step
    prog.art.jitted = {k: wrap(f) for k, f in prog.art.jitted.items()}


def _half_batch(prog):
    def wrap(fn):
        def step(p, o, c, i, k, batch):
            t = batch["labels"].shape[1]
            labels = batch["labels"].at[:, t // 2:].set(-1)
            return fn(p, o, c, i, k, dict(batch, labels=labels))
        return step
    prog.art.jitted = {k: wrap(f) for k, f in prog.art.jitted.items()}


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_under_the_timed_path_is_caught(fault):
    r = _run(tiny.cell(), fault)
    assert not r["correct"], r["checks"]


DP4 = """
import json, sys
sys.path[:0] = [{chip!r}, {here!r}]
import run, tiny
hook = None
{hook}
r = run.run_cell(tiny.cell(chips=4), {seed}, 0.5, False,
                 device=dict(platform="cpu", kind="cpu", count=4),
                 program_hook=hook)
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
"""

# the integer all-reduce left out: each worker unpacks its own words alone
NO_EXCHANGE = """
def hook(prog):
    from repro.parallel import collectives
    collectives.psum_wire_words = lambda words, axes: words
"""


def _four_workers(hook=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = DP4.format(chip=CHIP, here=HERE, seed=SEED, hook=hook)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_workers_agree_with_the_reference():
    r = _four_workers()
    assert r["correct"] is True, r["checks"]


def test_four_workers_without_the_exchange_are_caught():
    r = _four_workers(NO_EXCHANGE)
    assert r["correct"] is False, r["checks"]


def _batches(rng, rows, seq=32, steps=3):
    out = []
    for _ in range(steps):
        tokens = rng.integers(0, tiny.CONFIG["vocab"], (rows, seq), np.int32)
        labels = np.roll(tokens, -1, axis=1)
        labels[:, -1] = -1
        out.append((tokens, labels))
    return out


def test_reference_without_the_exchange_fails_the_limits():
    """The reference's own "no_exchange" fault, as calibrate.py reads it
    for a cell on four chips, on three seeds."""
    cell = tiny.cell(chips=4)
    for seed in (11, 12, 13):
        batches = _batches(np.random.default_rng(seed), 8)
        ref = check.reference_readings(cell, seed, batches)
        got = check.reference_readings(cell, seed, batches,
                                       fault="no_exchange")
        judged = check.judge(cell, check.numbers(got, ref))
        assert not all(c["ok"] for c in judged.values()), judged
        # the exact first step exchanges floats and is not at fault
        assert got["grad0"] == ref["grad0"]
