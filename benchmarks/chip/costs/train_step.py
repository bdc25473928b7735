"""Operations one training step requires: the step's tokens times the
operations a token takes in the configuration's family
(``families/<family>.py::ops_per_token``: forward and backward, not
counting recomputation). Returns (operations, bytes) per step; the step's
bytes are not modelled (None).
"""
import bench


def cost(config: dict, traffic: dict, chips: int):
    t = traffic
    tokens = t["batch_per_chip"] * chips * t["seq_len"]
    return tokens * bench.family(config["family"]).ops_per_token(
        config, t["seq_len"]), None
