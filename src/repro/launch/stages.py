"""The train step's stages, as names the compiled program keeps.

Each stage is a ``jax.named_scope`` placed where its work happens, so that
every instruction of the compiled step carries the stage in its
``op_name`` metadata (``jit(step)/fwd_bwd/transpose(jvp())/dot_general``)
and a device trace can put each operation's time down to a stage:

  fwd_bwd   forward and backward (``launch/step.py::_forward_backward``)
  alpha     the α rule: α from its state (``IntSGD._alphas``) and the Δx
            statistics that advance it (``launch/step.py::_observe_dx``)
  encode    stochastic rounding and the clip to the integer range
            (``IntSGD.encode_ints``, ``IntDIANA.encode_ints``)
  wire      pack, collective and unpack (``CommCtx.psum_wire``), and the
            exact step's float mean (``aggregate_exact``)
  decode    the summed integers back to a float gradient
  clip      the global-norm clip (``launch/step.py::_clip_factor``)
  update    the optimizer: ZeRO-1 and its all-gather, or the fused Pallas
            route with its pads and reshapes
  counters  the wire-width statistics the step returns (max |int|, bits)

Scopes are compile-time metadata: they change no instruction of the
compiled program, only its names, so there is no switch. Stages never
nest. A call site enters one with ``stages.stage(name)``, or
``@stages.scoped(name)`` around a whole function, always through this
module, so that a test can replace ``stage``. Compressors outside the IntSGD
family keep their ``aggregate`` unscoped, apart from the ``wire`` that
``CommCtx.psum_wire`` gives them.
"""
from __future__ import annotations

import functools

import jax

STAGES = ("fwd_bwd", "alpha", "encode", "wire", "decode", "clip",
          "update", "counters")


def stage(name: str):
    """A ``jax.named_scope`` for one of ``STAGES``."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages are {STAGES}")
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: the function's work runs in stage `name`. The scope is
    looked up at each call, so a replaced ``stage`` applies here too."""
    stage(name)  # refuse an unknown name where the function is defined

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with stage(name):
                return fn(*args, **kwargs)

        return inner

    return wrap
