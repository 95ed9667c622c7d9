"""Mixture-of-Experts: top-k routing with capacity-bounded scatter dispatch.

Dispatch is gather/scatter-based (not the one-hot einsum, whose (B,T,E,Cap)
tensor is quadratic-memory), so active-FLOPs in the compiled HLO match
6·N_active·D — keeping the roofline honest.  Expert weights are stacked along
a leading E axis and sharded over the 'model' mesh axis (expert parallelism);
the per-example clipping engines see them through ``dense_stacked``.

Router load-balance aux loss is computed PER EXAMPLE and added to the CE loss
before clipping — so the DP guarantee covers the router gradient too (see
DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..core import layers as L
from ..core.tape import Tape, scan_blocks
from ..obs.phases import block
from . import common as cm


def moe_params(key, d_model: int, n_experts: int, d_ff: int,
               n_router: int):
    """The router over ``n_router`` experts and the ``n_experts`` held
    experts' stacked SwiGLU weights."""
    ks = jax.random.split(key, 4)
    s = d_model ** -0.5
    return {
        "router": cm.dense_params(ks[0], d_model, n_router, scale=s),
        "w1": {"w": jax.random.normal(ks[1], (n_experts, d_model, d_ff)) * s},
        "w3": {"w": jax.random.normal(ks[2], (n_experts, d_model, d_ff)) * s},
        "w2": {"w": jax.random.normal(ks[3], (n_experts, d_ff, d_model)) * d_ff ** -0.5},
    }


def moe_block(tape: Tape, scope: str, path: str, p, x, cfg: ArchConfig,
              min_cap: int = 1):
    """x (B,T,D) -> (out (B,T,D), aux_loss (B,)).

    The layer holds ``cfg.n_experts`` experts, those numbered
    ``cfg.expert_offset`` on, of the ``cfg.n_router`` the router chooses
    among (an expert-parallel deployment's share; all of them by default).
    Routing is over the router's full width; dispatch, the expert matmuls
    and the combine cover the held experts only, so ``out`` is their part
    of the layer's result.  The capacity of an expert is per example,
    ceil(T·K·cf / n_router), so each row's output depends on that row
    alone.

    ``min_cap`` floors the per-expert capacity: chunked decode passes the
    chunk length T so no token is ever dropped (top-k picks distinct experts
    per token, so an expert receives at most T assignments per row) — a
    token routed alone (T=1, never dropped) must not be dropped just because
    it arrived inside a prefill chunk, or chunked serving would diverge from
    per-token decoding."""
    B, T, D = x.shape
    E, ER, K, off = cfg.n_experts, cfg.n_router, cfg.top_k, cfg.expert_offset
    cap = max(min_cap, math.ceil(T * K * cfg.capacity_factor / ER))

    with block("moe/route"):
        # a router dtype of its own is computed at full precision (a TPU
        # multiplies float32 in one bfloat16 pass by default)
        xr, prec = x, None
        if cfg.router_dtype:
            xr, prec = x.astype(cfg.router_dtype), jax.lax.Precision.HIGHEST
        logits = L.dense(tape, f"{scope}.router", xr, p["router"]["w"],
                         param_path=f"{path}.router", precision=prec)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (B,T,ER)
        topv, topi = jax.lax.top_k(probs, K)                        # (B,T,K)
        if cfg.norm_topk_prob:
            topv = topv / (topv.sum(-1, keepdims=True) + 1e-9)

        # ---- position-in-expert over the T*K virtual-token axis ----
        e_flat = topi.reshape(B, T * K)
        oh = jax.nn.one_hot(e_flat, ER, dtype=jnp.int32)            # (B,TK,ER)
        pos = jnp.cumsum(oh, axis=1) - oh                            # exclusive
        pos = jnp.take_along_axis(pos, e_flat[..., None], -1)[..., 0]  # (B,TK)
        local = e_flat - off
        valid = (pos < cap) & (local >= 0) & (local < E)
        idx = jnp.where(valid, local * cap + pos, E * cap)           # E*cap = drop

        # ---- per-example load-balance aux loss over the whole router ----
        f = oh.astype(jnp.float32).mean(axis=1)                      # (B,ER)
        pmean = probs.mean(axis=1)                                   # (B,ER)
        aux = ER * jnp.sum(f * pmean, axis=-1) * cfg.router_aux_coef  # (B,)

    # ---- dispatch: scatter tokens into per-expert capacity buffers ----
    with block("moe/dispatch"):
        x_rep = jnp.repeat(x, K, axis=1)                             # (B,TK,D)

        def scatter_one(xi, ii):
            return jnp.zeros((E * cap, D), x.dtype).at[ii].add(
                xi, mode="drop")
        buf = jax.vmap(scatter_one)(x_rep, idx)                      # (B,E*cap,D)
        buf = buf.reshape(B, E, cap, D).transpose(1, 0, 2, 3)        # (E,B,cap,D)
        buf = cm.maybe_shard_expert(buf)

    # ---- expert computation (stacked over E -> expert parallel) ----
    with block("moe/experts"):
        # w1/w3 share the dispatch buffer: record it once (halves MoE records)
        g, u = L.dense_stacked_pair(tape, f"{scope}.w13", buf,
                                    p["w1"]["w"], p["w3"]["w"],
                                    param_path1=f"{path}.w1",
                                    param_path2=f"{path}.w3")
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        yb = L.dense_stacked(tape, f"{scope}.w2", h, p["w2"]["w"],
                             param_path=f"{path}.w2")                # (E,B,cap,D)

    # ---- combine: gather back, weight by gates ----
    with block("moe/combine"):
        yb = yb.transpose(1, 0, 2, 3).reshape(B, E * cap, D)
        gathered = jnp.take_along_axis(
            yb, jnp.minimum(idx, E * cap - 1)[..., None], axis=1)    # (B,TK,D)
        w = (topv.reshape(B, T * K) * valid.astype(jnp.float32))
        y = (gathered.astype(jnp.float32) * w[..., None]).reshape(B, T, K, D)
        y = y.sum(axis=2).astype(x.dtype)
    return y, aux


class MoeLM:
    """OLMoE-style decoder LM: every FFN is a top-k MoE."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.acfg = cm.AttnCfg(
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
            qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
            rope_theta=cfg.rope_theta, window=cfg.sliding_window)

    def init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 4)

        def one_block(k):
            k1, k2 = jax.random.split(k)
            return {"ln1": cm.norm_params(cfg.d_model),
                    "attn": cm.attn_params(k1, cfg.d_model, self.acfg),
                    "ln2": cm.norm_params(cfg.d_model),
                    "moe": moe_params(k2, cfg.d_model, cfg.n_experts,
                                      cfg.moe_d_ff or cfg.d_ff, cfg.n_router)}

        return {
            "emb": {"w": jax.random.normal(ks[0], (cfg.vocab, cfg.d_model)) * 0.02},
            "blocks": cm.stacked_init(one_block, ks[1], cfg.n_layers),
            "lnf": cm.norm_params(cfg.d_model),
            "head": cm.dense_params(ks[2], cfg.d_model, cfg.vocab),
        }

    def _block(self, sub: Tape, p, x, aux, positions):
        x = cm.maybe_shard(x)
        h = cm.rmsnorm(sub, "ln1", x, p["ln1"], path="blocks.ln1")
        a, _ = cm.attention(sub, "attn", "blocks.attn", p["attn"], h, self.acfg,
                            positions=positions)
        x = x + a
        h = cm.rmsnorm(sub, "ln2", x, p["ln2"], path="blocks.ln2")
        y, aux_l = moe_block(sub, "moe", "blocks.moe", p["moe"], h, self.cfg)
        return x + y, aux + aux_l

    def backbone_aux(self, params, tokens, tape: Tape):
        cfg = self.cfg
        x = L.embed(tape, "emb", tokens, params["emb"]["w"], param_path="emb.w")
        x = x.astype(cfg.act_dtype)
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32),
                                     tokens.shape)

        def body(sub, p, carry):
            x, aux = carry
            return self._block(sub, p, x, aux, positions)

        x, aux = scan_blocks(tape, "blocks", body, params["blocks"],
                             (x, jnp.zeros(tokens.shape[0], jnp.float32)),
                             cfg.n_layers)
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

    # -- serving -------------------------------------------------------------
    def init_cache(self, params, B, S, dtype=jnp.bfloat16, **extras):
        c = cm.init_attn_cache(B, S, self.acfg, dtype)
        n = self.cfg.n_layers
        return {"blocks": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape), c)}

    def _decode_core(self, params, cache, tokens, pos, valid):
        cfg = self.cfg
        T = tokens.shape[1]
        tape = Tape()
        x = L.embed(tape, "emb", tokens, params["emb"]["w"], param_path="emb.w")
        x = x.astype(cfg.act_dtype)

        def step(carry, xs):
            p, c = xs
            t = Tape()
            h = cm.rmsnorm(t, "ln1", carry, p["ln1"], path="-")
            a, nc = cm.attention(t, "attn", "-", p["attn"], h, self.acfg,
                                 cache=c, pos=pos, valid=valid)
            carry = carry + a
            t2 = Tape()
            h = cm.rmsnorm(t2, "ln2", carry, p["ln2"], path="-")
            y, _ = moe_block(t2, "moe", "-", p["moe"], h, self.cfg, min_cap=T)
            return carry + y, nc

        x, new_blocks = jax.lax.scan(step, x, (params["blocks"], cache["blocks"]))
        x = cm.rmsnorm(Tape(), "lnf", x, params["lnf"], path="lnf")
        return x, {"blocks": new_blocks}

    def decode_step(self, params, cache, tokens, pos):
        x, new_cache = self._decode_core(params, cache, tokens, pos, None)
        logits = L.dense(Tape(), "head", x, params["head"]["w"], param_path="head")
        return logits[:, 0], new_cache

    def prefill_step(self, params, cache, tokens, pos, n_tok):
        """Chunked prefill (see DenseLM.prefill_step); the MoE capacity is
        floored at the chunk length so no in-chunk token is dropped."""
        x, new_cache = self._decode_core(params, cache, tokens, pos,
                                         cm.chunk_valid(tokens, n_tok))
        xl = cm.gather_last(x, n_tok)
        logits = L.dense(Tape(), "head", xl, params["head"]["w"],
                         param_path="head")
        return logits[:, 0], new_cache
