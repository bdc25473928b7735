"""A cell at a size the CPU runs in seconds, for the harness's tests."""
import bench

CONFIG = {
    "name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
    "vocab": 256, "window": None, "rope_theta": 10000.0,
    "tie_embeddings": False, "qkv_bias": False,
}
TRAFFIC = {
    "data_parallel": 1, "batch_per_chip": 2, "seq_len": 32,
    "compressor": "intsgd8", "wire": "packed8", "bits": 8, "route": "zero1",
    "step0_program": "exact", "lr": 0.3, "warmup_steps": 5,
    "momentum": 0.9, "weight_decay": 1e-4, "clip_norm": 1.0,
    "overlap": "off", "microbatches": 1, "check_steps": 3,
}
# Set by calibrate.py at this size on the CPU: the sound program's largest
# readings over 12 seeds were loss 2.1e-4, grad0 1.6e-3, change 9.2e-4 and
# change_median 2.2e-4; the float8 control's smallest over 3 seeds 7.1e-4,
# 7.4e-3, 5.9e-3 and 1.1e-3.
LIMITS = {"loss": 5e-4, "grad0": 4.5e-3, "change": 3.5e-3, "change_median": 5.8e-4,
          "window_compiles": 0}


def cell(chips=1, **traffic):
    t = dict(TRAFFIC, data_parallel=chips, **traffic)
    return bench.Cell(name="tiny", chips=chips, config=CONFIG, traffic=t,
                      limits=LIMITS, end_to_end=(), per_layer=())
