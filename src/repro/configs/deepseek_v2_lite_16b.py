"""deepseek-v2-lite-16b [moe]: 27L d_model=2048 16H vocab=102400, untied
head. MLA: kv_lora 512 (latent RMSNorm), no q_lora, qk 128 content + 64
rotary dims, v 128; rotary theta 10000 with YaRN (factor 40, original
4096, beta 32/1, mscale = mscale_all_dim = 0.707). Layer 0 is dense
(SwiGLU 10944); layers 1-26 are MoE: 64 routed experts of width 1408, top-6
of a softmax over all 64 without renormalisation, 2 shared experts (one
SwiGLU of width 2816), sequence-wise balance loss, no token dropped.
[arXiv:2405.04434; huggingface deepseek-ai/DeepSeek-V2-Lite config.json]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab=102400,
    head_dim=128,
    rope_theta=10000.0,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    norm_topk_prob=False,
    aux_loss_alpha=0.001,
    first_k_dense=1,
    d_ff_dense=10944,
    kv_lora=512,
    yarn_factor=40.0,
    yarn_original_max_position=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    source="arXiv:2405.04434",
)
