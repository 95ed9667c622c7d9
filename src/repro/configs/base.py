"""ArchConfig: one dataclass describing every supported architecture, plus the
four assigned input shapes and their ShapeDtypeStruct input specs."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str               # dense | moe | ssm | hybrid | vlm | audio | vit
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    sliding_window: int = 0          # >0: sliding-window attention + ring cache
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    n_router_experts: int = 0        # router width; 0: equal to n_experts.
    expert_offset: int = 0           # with it, n_experts counts the experts
                                     # held, [offset, offset + n_experts)
    norm_topk_prob: bool = True      # renormalise the top-k gates to sum 1
    router_dtype: str = ""           # router logits' dtype; "": activations'
    moe_d_ff: int = 0                # per-expert hidden (if != d_ff)
    first_dense_layers: int = 0      # deepseek: leading dense FFN layers
    dense_d_ff: int = 0              # FFN width of those leading dense layers
    # MLA (deepseek-v2)
    kv_lora: int = 0
    rope_dim: int = 0
    # YaRN rope scaling (deepseek-v2); factor 0: plain RoPE
    rope_scaling_factor: float = 0.0
    rope_original_max_pos: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # SSM (mamba2 / zamba2)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4
    ssm_chunk: int = 256
    # hybrid (zamba2)
    attn_every: int = 0              # shared attn block once per this many ssm layers
    # vlm
    cross_every: int = 0             # one cross-attn layer per this many layers
    n_image_tokens: int = 0
    frontend_dim: int = 0
    # audio (whisper enc-dec)
    n_audio_frames: int = 0
    n_encoder_layers: int = 0
    # vit (paper's model)
    image_size: int = 0
    patch: int = 16
    n_classes: int = 0
    # numerics
    dtype: str = "bfloat16"
    remat: bool = False
    ce_chunk: int = 0      # >0: chunk the head+CE over T (big-vocab memory)

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def n_router(self) -> int:
        """Experts the router chooses among (all of them, held or not)."""
        return self.n_router_experts or self.n_experts

    @property
    def act_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def d_inner(self) -> int:           # ssm
        return self.ssm_expand * self.d_model

    @property
    def nheads_ssm(self) -> int:
        return self.ssm_heads or (self.d_inner // self.ssm_head_dim)

    def reduced(self, **over) -> "ArchConfig":
        """Smoke-test variant: same family/feature set, tiny dims."""
        small = dict(
            n_layers=2, d_model=128, n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            d_ff=256, vocab=97, head_dim=32,
            n_experts=4 if self.n_experts else 0,
            n_router_experts=0, expert_offset=0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            moe_d_ff=64 if self.n_experts else 0,
            n_shared_experts=min(self.n_shared_experts, 1),
            first_dense_layers=min(self.first_dense_layers, 1),
            kv_lora=32 if self.kv_lora else 0,
            rope_dim=16 if self.rope_dim else 0,
            ssm_state=16 if self.ssm_state else 0,
            ssm_head_dim=32 if self.ssm_state else 64,
            ssm_chunk=8,
            attn_every=1 if self.attn_every else 0,
            cross_every=2 if self.cross_every else 0,
            n_image_tokens=8 if self.n_image_tokens else 0,
            frontend_dim=48 if self.frontend_dim else 0,
            n_audio_frames=12 if self.n_audio_frames else 0,
            n_encoder_layers=2 if self.n_encoder_layers else 0,
            image_size=32 if self.image_size else 0, patch=8,
            sliding_window=16 if self.sliding_window else 0,
            dtype="float32",
            name=self.name + "-smoke",
        )
        small.update(over)
        return dataclasses.replace(self, **small)


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str        # train | prefill | decode


SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def input_specs(cfg: ArchConfig, shape: InputShape):
    """ShapeDtypeStruct stand-ins for every model input of the given shape.

    train/prefill -> kwargs for train_step(state, batch, mask)
    decode        -> kwargs for serve_step(params, cache, tokens, pos)
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f = cfg.act_dtype
    if shape.kind in ("train", "prefill"):
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                 "labels": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.family == "vlm":
            batch["frontend"] = jax.ShapeDtypeStruct(
                (B, cfg.n_image_tokens, cfg.frontend_dim), f)
        if cfg.family == "audio":
            batch["frontend"] = jax.ShapeDtypeStruct(
                (B, cfg.n_audio_frames, cfg.d_model), f)
        if cfg.family == "vit":
            batch = {"image": jax.ShapeDtypeStruct(
                        (B, cfg.image_size, cfg.image_size, 3), f),
                     "label": jax.ShapeDtypeStruct((B,), i32)}
        mask = jax.ShapeDtypeStruct((B,), jnp.float32)
        return {"batch": batch, "mask": mask}
    # decode: one new token against a KV/SSM cache of length S
    return {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
            "pos": jax.ShapeDtypeStruct((), i32)}
