"""mfu (train step, %): model FLOP/s utilisation of the whole step.

The operations one step requires (costs/train_step.py: 6 x matmul
parameters per token plus attention's causal products; recomputation not
counted), times the steps per second of the traced run's steps outside the
profiled ones, over chips x the chip's bf16 peak (peaks.json)."""


def read(ctx):
    c = ctx["cell"]
    ops, _ = ctx["cost"]("train_step")(c.config, c.traffic, c.chips)
    tokens_per_step = c.traffic["batch_per_chip"] * c.chips * c.traffic["seq_len"]
    steps_per_s = ctx["tokens_per_s"] / tokens_per_step
    return 100.0 * ops * steps_per_s / (c.chips * ctx["peaks"]["bf16_flops_per_s"])
