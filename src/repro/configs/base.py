"""Architecture + shape registry.

Each assigned architecture lives in its own module (one file per arch, per
the deliverable structure) and registers an exact ``ModelConfig``. Shapes are
shared by all LM-family archs. ``smoke_config`` derives a reduced same-family
config for CPU tests; full configs are only ever lowered via the dry-run.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | vlm | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    window: Optional[int] = None  # sliding-window attention
    rope_theta: float = 10000.0
    # MoE. ``n_experts`` is the router's width; ``d_ff`` the width of one
    # routed expert, and n_shared_experts * d_ff the shared SwiGLU's.
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    # experts held on this chip, the first n_experts_held of the router's
    # (one chip's share under expert parallelism); 0: all of them
    n_experts_held: int = 0
    norm_topk_prob: bool = True  # renormalise the top-k gate weights
    aux_loss_alpha: float = 0.0  # sequence-wise balance loss coefficient
    # leading dense layers before the MoE stack, and their SwiGLU width
    first_k_dense: int = 0
    d_ff_dense: int = 0
    # MLA
    kv_lora: int = 0
    # YaRN rotary scaling (0: none), as DeepSeek-V2's rope_scaling gives it
    yarn_factor: float = 0.0
    yarn_original_max_position: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # hybrid / ssm
    ssm_state: int = 0
    attn_every: int = 0
    # enc-dec
    enc_layers: int = 0
    dec_layers: int = 0
    # modality frontend stub
    frontend: Optional[str] = None  # vit | audio
    frontend_dim: int = 0
    n_frontend_tokens: int = 0
    tie_embeddings: bool = False
    # remat policy for the layer scan: "full" (recompute everything) or
    # "save_psum" (save TP collective outputs — trades activation memory for
    # a third of the TP all-reduce traffic; see EXPERIMENTS.md §Perf)
    remat_policy: str = "full"
    # provenance
    source: str = ""

    @property
    def subquadratic(self) -> bool:
        """Eligible for long_500k (SSM / hybrid / SWA families)."""
        return self.family in ("ssm", "hybrid") or self.window is not None

    @property
    def has_decoder(self) -> bool:
        return True  # all ten assigned archs have a decode path


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

_ARCH_MODULES = [
    "qwen2_5_32b",
    "granite_8b",
    "minitron_4b",
    "h2o_danube_3_4b",
    "zamba2_2_7b",
    "internvl2_2b",
    "deepseek_v2_lite_16b",
    "mixtral_8x22b",
    "xlstm_125m",
    "seamless_m4t_medium",
]

ARCHS: dict[str, ModelConfig] = {}


def _load():
    if ARCHS:
        return
    for m in _ARCH_MODULES:
        mod = importlib.import_module(f"repro.configs.{m}")
        cfg = mod.CONFIG
        ARCHS[cfg.name] = cfg


def get_arch(name: str) -> ModelConfig:
    _load()
    if name not in ARCHS:
        raise ValueError(f"unknown arch {name!r}; options {sorted(ARCHS)}")
    return ARCHS[name]


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise ValueError(f"unknown shape {name!r}; options {sorted(SHAPES)}")
    return SHAPES[name]


def runnable_cells() -> list[tuple[str, str, bool]]:
    """All 40 (arch, shape) cells with a runnable flag.
    long_500k is skipped for pure full-attention archs (see DESIGN.md)."""
    _load()
    out = []
    for a, cfg in ARCHS.items():
        for s in SHAPES:
            runnable = True
            if s == "long_500k" and not cfg.subquadratic:
                runnable = False
            out.append((a, s, runnable))
    return out


def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    kw = dict(
        name=cfg.name + "-smoke",
        n_layers=min(cfg.n_layers, 4 if cfg.family != "hybrid" else 4),
        d_model=64,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        d_ff=128 if cfg.d_ff else 0,
        vocab=256,
        head_dim=16,
        kv_lora=32 if cfg.kv_lora else 0,
        n_experts=4 if cfg.n_experts else 0,
        top_k=min(cfg.top_k, 2) if cfg.top_k else 0,
        n_shared_experts=min(cfg.n_shared_experts, 1),
        n_experts_held=min(cfg.n_experts_held, 2),
        first_k_dense=min(cfg.first_k_dense, 1),
        d_ff_dense=128 if cfg.d_ff_dense else 0,
        ssm_state=16 if cfg.ssm_state else 0,
        attn_every=2 if cfg.attn_every else 0,
        enc_layers=2 if cfg.enc_layers else 0,
        dec_layers=2 if cfg.dec_layers else 0,
        frontend_dim=32 if cfg.frontend_dim else 0,
        n_frontend_tokens=8 if cfg.n_frontend_tokens else 0,
        window=64 if cfg.window else None,
    )
    if cfg.family == "hybrid":
        kw["n_layers"] = 4  # 2 blocks x attn_every=2
    if cfg.family == "ssm":
        kw["n_layers"] = 3  # one (m,m,s) block
        kw["head_dim"] = 16
    return dataclasses.replace(cfg, **kw)
