"""DeepSeek-V2-Lite 16B: MLA (kv_lora=512, no q-LoRA, YaRN rope scaling) +
MoE (2 shared + 64 routed, top-6, softmax gate without renormalisation)
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json].
``router_aux_coef`` is the published ``aux_loss_alpha`` (sequence-level)."""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, moe_d_ff=1408, vocab=102400,
    n_experts=64, top_k=6, n_shared_experts=2,
    norm_topk_prob=False, router_dtype="float32", router_aux_coef=0.001,
    kv_lora=512, rope_dim=64, head_dim=128,
    first_dense_layers=1, dense_d_ff=10944,
    rope_theta=1e4, rope_scaling_factor=40.0, rope_original_max_pos=4096,
    rope_beta_fast=32.0, rope_beta_slow=1.0,
    rope_mscale=0.707, rope_mscale_all_dim=0.707,
)
