"""fwd_bwd_ms (model, ms): device time per step of the forward and
backward pass, the operations the compiled step names under the stage
``fwd_bwd`` (``launch/step.py::_forward_backward``), averaged over chips."""

import stages


def read(ctx):
    return stages.ms(ctx, ("fwd_bwd",))
