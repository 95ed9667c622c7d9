"""Multi-head Latent Attention (DeepSeek-V2) and the DeepseekV2 MoE LM.

MLA compresses K/V into a rank-``kv_lora`` latent c plus a small shared RoPE
key.  The decode cache stores only (c, k_rope) — (kv_lora + rope_dim) floats
per token instead of 2·H·Dh — and decoding uses the absorbed-matmul form
(scores against c directly), which is the arch's whole point.

Rotary positions may be YaRN-scaled (:func:`rope_scaling`).  The rope dims
rotate in the half-split form; the published model de-interleaves each
head's rope pairs first, which is the same up to a fixed permutation of
the rope columns of ``wq`` and ``wkr``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..core import layers as L
from ..core.tape import Tape, scan_blocks
from ..obs.phases import block
from . import common as cm
from .moe import moe_block, moe_params

QK_NOPE = 128
V_HEAD = 128


def mla_params(key, cfg: ArchConfig):
    D, H = cfg.d_model, cfg.n_heads
    r, rd = cfg.kv_lora, cfg.rope_dim
    nope, vh = min(QK_NOPE, cfg.hd), min(V_HEAD, cfg.hd)
    ks = jax.random.split(key, 5)
    return {
        "wq": cm.dense_params(ks[0], D, H * (nope + rd)),
        "wdkv": cm.dense_params(ks[1], D, r),
        "ckv_norm": cm.norm_params(r),
        "wukv": cm.dense_params(ks[2], r, H * (nope + vh)),
        "wkr": cm.dense_params(ks[3], D, rd),
        "wo": cm.dense_params(ks[4], H * vh, D),
    }


def _dims(cfg: ArchConfig):
    nope, vh = min(QK_NOPE, cfg.hd), min(V_HEAD, cfg.hd)
    return nope, vh, cfg.rope_dim


def rope_scaling(cfg: ArchConfig):
    """(inverse frequencies of the rope dims or None for plain RoPE, the
    cos/sin factor, the softmax scale).  With YaRN (``rope_scaling_factor``
    set) the frequencies ramp between the correction range's ends, cos
    and sin take mscale(factor, mscale) / mscale(factor, mscale_all_dim),
    and the scale (nope + rope)^-1/2 takes mscale(factor, mscale_all_dim)²,
    as DeepSeek-V2 does."""
    nope, _, rd = _dims(cfg)
    scl = (nope + rd) ** -0.5
    f = cfg.rope_scaling_factor
    if not f:
        return None, 1.0, scl
    freqs = cm.yarn_freqs(rd, cfg.rope_theta, f, cfg.rope_original_max_pos,
                          cfg.rope_beta_fast, cfg.rope_beta_slow)
    all_dim = cm.yarn_mscale(f, cfg.rope_mscale_all_dim)
    if cfg.rope_mscale_all_dim:
        scl = scl * all_dim * all_dim
    return freqs, cm.yarn_mscale(f, cfg.rope_mscale) / all_dim, scl


def mla_attention(tape: Tape, scope: str, path: str, p, x, cfg: ArchConfig,
                  positions):
    """Training/prefill MLA (full sequence, causal)."""
    with block("mla/attention"):
        return _mla_attention(tape, scope, path, p, x, cfg, positions)


def _mla_attention(tape, scope, path, p, x, cfg, positions):
    B, T, D = x.shape
    H = cfg.n_heads
    nope, vh, rd = _dims(cfg)
    freqs, rope_m, scl = rope_scaling(cfg)

    q = L.dense(tape, f"{scope}.wq", x, p["wq"]["w"], param_path=f"{path}.wq")
    q = q.reshape(B, T, H, nope + rd)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    c = L.dense(tape, f"{scope}.wdkv", x, p["wdkv"]["w"],
                param_path=f"{path}.wdkv")
    c = cm.rmsnorm(tape, f"{scope}.ckv_norm", c, p["ckv_norm"],
                   path=f"{path}.ckv_norm")
    kv = L.dense(tape, f"{scope}.wukv", c, p["wukv"]["w"],
                 param_path=f"{path}.wukv").reshape(B, T, H, nope + vh)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = L.dense(tape, f"{scope}.wkr", x, p["wkr"]["w"],
                     param_path=f"{path}.wkr").reshape(B, T, 1, rd)

    q_rope = cm.apply_rope(q_rope, positions, cfg.rope_theta, freqs, rope_m)
    k_rope = cm.apply_rope(k_rope, positions, cfg.rope_theta, freqs, rope_m)

    # one head of (nope + rope) dims against the shared rope key: the
    # dense layers' attention, at MLA's own softmax scale
    qh = jnp.concatenate([q_nope, q_rope], axis=-1)
    kh = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, T, H, rd))],
                         axis=-1)
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    o = cm._sdpa(qh[:, :, :, None], kh, v, mask, scale=scl)   # (B,T,H,1,vh)
    return L.dense(tape, f"{scope}.wo", o.reshape(B, T, H * vh),
                   p["wo"]["w"], param_path=f"{path}.wo")


def mla_decode(p, x, cfg: ArchConfig, cache, pos, valid=None):
    """Absorbed-matmul decode against the (c, k_rope) cache, over a chunk
    of T >= 1 tokens at per-slot offsets (T == 1 is plain decode).
    ``pos`` is a scalar or a (B,) vector of per-slot start positions;
    ``valid`` (B,T) masks unconsumed chunk-tail tokens (their cache writes
    are dropped)."""
    with block("mla/attention"):
        return _mla_decode(p, x, cfg, cache, pos, valid)


def _mla_decode(p, x, cfg, cache, pos, valid):
    B, T, D = x.shape
    H = cfg.n_heads
    nope, vh, rd = _dims(cfg)
    r = cfg.kv_lora
    freqs, rope_m, scl = rope_scaling(cfg)
    posb = cm.decode_positions(pos, B)                     # (B,)
    tok_pos = posb[:, None] + jnp.arange(T, dtype=jnp.int32)   # (B,T)

    q = (x @ p["wq"]["w"]).reshape(B, T, H, nope + rd)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = cm.apply_rope(q_rope, tok_pos, cfg.rope_theta, freqs, rope_m)

    c1 = x @ p["wdkv"]["w"]
    c1f = c1.astype(jnp.float32)
    c1 = (c1f * jax.lax.rsqrt(jnp.mean(c1f * c1f, -1, keepdims=True) + 1e-6)
          ).astype(x.dtype) * p["ckv_norm"]["w"].astype(x.dtype)
    kr1 = (x @ p["wkr"]["w"]).reshape(B, T, 1, rd)
    kr1 = cm.apply_rope(kr1, tok_pos, cfg.rope_theta, freqs, rope_m)

    cc = cm.scatter_rows(cache["c"], tok_pos, c1, valid)
    ckr = cm.scatter_rows(cache["kr"], tok_pos, kr1[:, :, 0], valid)
    S = cc.shape[1]

    wukv = p["wukv"]["w"].reshape(r, H, nope + vh)
    w_uk, w_uv = wukv[..., :nope], wukv[..., nope:]
    # absorb: q against latent space
    q_c = jnp.einsum("bthd,rhd->bthr", q_nope.astype(jnp.float32),
                     w_uk.astype(jnp.float32))
    s = (jnp.einsum("bthr,bsr->bhts", q_c, cc.astype(jnp.float32))
         + jnp.einsum("bthd,bsd->bhts", q_rope.astype(jnp.float32),
                      ckr.astype(jnp.float32))) * scl
    vis = jnp.arange(S)[None, None, :] <= tok_pos[:, :, None]  # (B,T,S)
    s = jnp.where(vis[:, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhts,bsr->bthr", a, cc.astype(jnp.float32))
    o = jnp.einsum("bthr,rhd->bthd", ctx, w_uv.astype(jnp.float32))
    o = o.astype(x.dtype).reshape(B, T, H * vh)
    out = o @ p["wo"]["w"].astype(x.dtype)
    return out, {"c": cc, "kr": ckr}


class DeepseekV2LM:
    """MLA attention + (2 shared + E routed top-k) MoE FFN; leading dense FFN
    layer(s) per the model card."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 5)
        shared_ff = cfg.n_shared_experts * (cfg.moe_d_ff or cfg.d_ff)

        def dense_block(k):
            k1, k2 = jax.random.split(k)
            return {"ln1": cm.norm_params(cfg.d_model),
                    "attn": mla_params(k1, cfg),
                    "ln2": cm.norm_params(cfg.d_model),
                    "mlp": cm.swiglu_params(k2, cfg.d_model,
                                            cfg.dense_d_ff or 4 * cfg.d_model)}

        def moe_blockp(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {"ln1": cm.norm_params(cfg.d_model),
                    "attn": mla_params(k1, cfg),
                    "ln2": cm.norm_params(cfg.d_model),
                    "moe": moe_params(k2, cfg.d_model, cfg.n_experts,
                                      cfg.moe_d_ff or cfg.d_ff, cfg.n_router),
                    "shared": cm.swiglu_params(k3, cfg.d_model, shared_ff)}

        nd = cfg.first_dense_layers
        return {
            "emb": {"w": jax.random.normal(ks[0], (cfg.vocab, cfg.d_model)) * 0.02},
            "dense_blocks": cm.stacked_init(dense_block, ks[1], nd),
            "moe_blocks": cm.stacked_init(moe_blockp, ks[2], cfg.n_layers - nd),
            "lnf": cm.norm_params(cfg.d_model),
            "head": cm.dense_params(ks[3], cfg.d_model, cfg.vocab),
        }

    def backbone_aux(self, params, tokens, tape: Tape):
        cfg = self.cfg
        x = L.embed(tape, "emb", tokens, params["emb"]["w"], param_path="emb.w")
        x = x.astype(cfg.act_dtype)
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32),
                                     tokens.shape)

        def dense_body(sub, p, x):
            x = cm.maybe_shard(x)
            h = cm.rmsnorm(sub, "ln1", x, p["ln1"], path="dense_blocks.ln1")
            x = x + mla_attention(sub, "attn", "dense_blocks.attn", p["attn"],
                                  h, cfg, positions)
            h = cm.rmsnorm(sub, "ln2", x, p["ln2"], path="dense_blocks.ln2")
            return x + cm.swiglu(sub, "mlp", "dense_blocks.mlp", p["mlp"], h)

        def moe_body(sub, p, carry):
            x, aux = carry
            x = cm.maybe_shard(x)
            h = cm.rmsnorm(sub, "ln1", x, p["ln1"], path="moe_blocks.ln1")
            x = x + mla_attention(sub, "attn", "moe_blocks.attn", p["attn"],
                                  h, cfg, positions)
            h = cm.rmsnorm(sub, "ln2", x, p["ln2"], path="moe_blocks.ln2")
            y, aux_l = moe_block(sub, "moe", "moe_blocks.moe", p["moe"], h, cfg)
            y = y + cm.swiglu(sub, "shared", "moe_blocks.shared", p["shared"], h)
            return x + y, aux + aux_l

        x = scan_blocks(tape, "dense_blocks", dense_body, params["dense_blocks"],
                        x, cfg.first_dense_layers)
        x, aux = scan_blocks(tape, "moe_blocks", moe_body, params["moe_blocks"],
                             (x, jnp.zeros(tokens.shape[0], jnp.float32)),
                             cfg.n_layers - cfg.first_dense_layers)
        return cm.rmsnorm(tape, "lnf", x, params["lnf"], path="lnf"), aux

    def logits_aux(self, params, tokens, tape: Tape, last_only: bool = False):
        x, aux = self.backbone_aux(params, tokens, tape)
        if last_only:
            x = x[:, -1:]
        return L.dense(tape, "head", x, params["head"]["w"],
                       param_path="head"), aux

    def loss(self, params, batch, tape: Tape):
        x, aux = self.backbone_aux(params, batch["tokens"], tape)
        return cm.lm_head_ce(tape, params["head"], x, batch["labels"],
                             self.cfg) + aux

    # -- serving --------------------------------------------------------------
    def init_cache(self, params, B, S, dtype=jnp.bfloat16, **extras):
        cfg = self.cfg
        one = {"c": jnp.zeros((B, S, cfg.kv_lora), dtype),
               "kr": jnp.zeros((B, S, cfg.rope_dim), dtype)}
        nd = cfg.first_dense_layers
        return {"dense_blocks": jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (nd,) + a.shape), one),
                "moe_blocks": jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (cfg.n_layers - nd,) + a.shape), one)}

    def _decode_core(self, params, cache, tokens, pos, valid):
        cfg = self.cfg
        T = tokens.shape[1]
        x = jnp.take(params["emb"]["w"], tokens, axis=0).astype(cfg.act_dtype)

        def rms(x, p):
            xf = x.astype(jnp.float32)
            return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
                    ).astype(x.dtype) * p["w"].astype(x.dtype)

        def dense_step(carry, xs):
            p, c = xs
            a, nc = mla_decode(p["attn"], rms(carry, p["ln1"]), cfg, c, pos,
                               valid=valid)
            carry = carry + a
            h = rms(carry, p["ln2"])
            carry = carry + cm.swiglu(Tape(), "mlp", "-", p["mlp"], h)
            return carry, nc

        def moe_step(carry, xs):
            p, c = xs
            a, nc = mla_decode(p["attn"], rms(carry, p["ln1"]), cfg, c, pos,
                               valid=valid)
            carry = carry + a
            h = rms(carry, p["ln2"])
            y, _ = moe_block(Tape(), "moe", "-", p["moe"], h, cfg, min_cap=T)
            y = y + cm.swiglu(Tape(), "shared", "-", p["shared"], h)
            return carry + y, nc

        x, ndc = jax.lax.scan(dense_step, x,
                              (params["dense_blocks"], cache["dense_blocks"]))
        x, nmc = jax.lax.scan(moe_step, x,
                              (params["moe_blocks"], cache["moe_blocks"]))
        x = rms(x, params["lnf"])
        return x, {"dense_blocks": ndc, "moe_blocks": nmc}

    def decode_step(self, params, cache, tokens, pos):
        x, new_cache = self._decode_core(params, cache, tokens, pos, None)
        logits = x @ params["head"]["w"].astype(x.dtype)
        return logits[:, 0], new_cache

    def prefill_step(self, params, cache, tokens, pos, n_tok):
        """Chunked prefill against the latent cache (see
        DenseLM.prefill_step)."""
        x, new_cache = self._decode_core(params, cache, tokens, pos,
                                         cm.chunk_valid(tokens, n_tok))
        xl = cm.gather_last(x, n_tok)
        logits = xl @ params["head"]["w"].astype(x.dtype)
        return logits[:, 0], new_cache
