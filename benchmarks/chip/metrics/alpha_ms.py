"""alpha_ms (compressor, ms): device time per step of IntSGD's adaptive α,
the operations under the stage ``alpha``: α from its state
(``IntSGD._alphas``) and the statistics of the model's change that advance
it (``launch/step.py::_observe_dx``), averaged over chips."""

import stages


def read(ctx):
    return stages.ms(ctx, ("alpha",))
