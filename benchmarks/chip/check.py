"""The comparison that decides `correct`.

The program's readings come from its own first steps, taken in set-up
through the window's call and feed: each step's loss, the leaf norms of the
first gradient as the optimizer received it, and the leaf norms of the
weights' change after the last of those steps. The reference runs the same
steps on the same batches (``reference.py``). Three numbers are compared,
each against the cell's limit (``cells/<cell>.json``):

  loss    max over steps |L_prog − L_ref| / |L_ref|
  grad0   max over leaves |‖g‖_prog − ‖g‖_ref| / max(‖g‖_ref, median leaf)
  change  the same for the change of the weights, over the leaves whose
          reference gradient is at least 1e-3 of the median leaf's: a leaf
          the loss does not reach moves by round-off alone
  change_median  the median of those leaves' gaps: steadier than the worst
          leaf's, and the number that separates the float8 control from
          sound runs where the worst leaf's does not (PERF.md)

and ``window_compiles``, the programs compiled inside the window, against 0.
"""
from __future__ import annotations

import statistics

import jax
import numpy as np

import reference

ZERO_GRAD = 1e-3  # below this share of the median leaf, a gradient is nought


def leaf_gaps(prog, ref) -> list:
    """Per leaf, |norm_prog - norm_ref| / max(norm_ref, median leaf's)."""
    med = statistics.median(ref)
    return [abs(p - r) / max(r, med) for p, r in zip(prog, ref)]


def worst_leaf(prog, ref, keep=None) -> float:
    gaps = leaf_gaps(prog, ref)
    if keep is not None:
        gaps = [g for g, k in zip(gaps, keep) if k]
    return float(max(gaps))


def numbers(prog: dict, ref: dict) -> dict:
    med = statistics.median(ref["grad0"])
    keep = [g >= ZERO_GRAD * med for g in ref["grad0"]]
    loss = max(abs(p - r) / abs(r)
               for p, r in zip(prog["losses"], ref["losses"]))
    change = [g for g, k in zip(leaf_gaps(prog["change"], ref["change"]), keep)
              if k]
    return {
        "loss": float(loss),
        "grad0": worst_leaf(prog["grad0"], ref["grad0"]),
        "change": float(max(change)),
        "change_median": float(statistics.median(change)),
    }


def reference_readings(cell, seed: int, batches, *, precision="float32",
                       fault=None) -> dict:
    """The reference's (or with precision "float8", the control's)
    readings of the cell's first steps on `batches`."""
    from program import prng_seed

    t = cell.traffic
    trainer = reference.Trainer(cell.config, t, precision=precision)
    first = t["step0_program"] == "exact"
    with jax.default_matmul_precision("highest"):
        return reference.run(
            trainer, jax.random.PRNGKey(prng_seed(seed)), batches,
            n_workers=t["data_parallel"],
            compressed=lambda k: t["bits"] is not None and (k > 0 or not first),
            noise_seed=seed, fault=fault,
        )


def judge(cell, nums: dict) -> dict:
    """Each number against the cell's limit. A limit of null marks a number
    that is printed and not compared: no control or fault separates it
    from sound runs at this cell's size (PERF.md)."""
    out = {}
    for name, value in nums.items():
        limit = cell.limits[name]
        ok = limit is None or (bool(np.isfinite(value)) and value <= limit)
        out[name] = {"value": value, "limit": limit, "ok": ok}
    return out


def compare(cell, seed: int, readings: dict, window_compiles: int) -> dict:
    ref = reference_readings(cell, seed, readings["batches"])
    if readings["leaves"] != ref["leaves"]:
        raise RuntimeError(
            f"the program's parameter tree {readings['leaves']} is not the "
            f"reference's {ref['leaves']}")
    nums = numbers(readings, ref)
    nums["window_compiles"] = window_compiles
    return judge(cell, nums)
