"""Shared model components, all built on the DP layer primitives.

Everything with parameters routes through ``repro.core.layers`` so that every
architecture is ghost/BK-clippable without per-arch DP code.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import layers as L
from ..core.tape import Tape


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_params(key, din, dout, use_bias=False, scale=None):
    s = scale if scale is not None else din ** -0.5
    p = {"w": jax.random.normal(key, (din, dout), jnp.float32) * s}
    if use_bias:
        p["b"] = jnp.zeros((dout,), jnp.float32)
    return p


def norm_params(dim):
    return {"w": jnp.ones((dim,), jnp.float32)}


def stacked_init(init_one, key, n):
    """vmap an init function over n layer keys -> stacked param tree."""
    return jax.vmap(init_one)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(tape: Tape, name: str, x, p, *, path: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    xhat = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return L.scale(tape, name, xhat.astype(x.dtype), p["w"], param_path=f"{path}.w")


def layernorm(tape: Tape, name: str, x, p, *, path: str, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    xhat = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    h = L.scale(tape, f"{name}.g", xhat, p["g"]["w"], param_path=f"{path}.g.w")
    return L.bias(tape, f"{name}.b", h, p["b"]["w"], param_path=f"{path}.b.w")


def layernorm_params(dim):
    return {"g": {"w": jnp.ones((dim,), jnp.float32)},
            "b": {"w": jnp.zeros((dim,), jnp.float32)}}


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature 0.1·mscale·ln(factor) + 1 (1 when the
    context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, original_max_pos: int,
                          beta_fast: float, beta_slow: float):
    """The rotary pairs (low, high) between which YaRN ramps: pair i turns
    original_max_pos·theta^(-2i/dim)/(2π) times over the original context;
    those turning more than ``beta_fast`` times end at ``low``, those
    turning fewer than ``beta_slow`` times start at ``high``."""
    def pair(rotations):
        return (dim * math.log(original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), dim - 1))


def yarn_freqs(dim: int, theta: float, factor: float, original_max_pos: int,
               beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies (arXiv:2309.00071, DeepSeek-V2's form):
    pairs up to ``low`` keep theirs, pairs from ``high`` on are divided by
    ``factor``, and those between follow the linear ramp."""
    low, high = yarn_correction_range(dim, theta, original_max_pos,
                                      beta_fast, beta_slow)
    if low == high:
        high += 0.001
    extra = rope_freqs(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x, positions, theta: float, freqs=None, mscale: float = 1.0):
    """x (..., T, H, Dh), positions (..., T) int32; ``freqs`` (Dh/2,)
    overrides the plain inverse frequencies and ``mscale`` multiplies
    cos and sin (YaRN)."""
    dh = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(dh, theta)                     # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, Dh/2)
    cos = jnp.cos(ang)[..., None, :]                      # (..., T, 1, Dh/2)
    sin = jnp.sin(ang)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, qk-norm, sliding window, cross, KV cache)
# ---------------------------------------------------------------------------

# Sequences at or above this length use blocked flash attention (never
# materialise the T x S score matrix). Tunable from the dry-run (§Perf).
FLASH_MIN_T = 8192


def set_flash_min_t(n: int) -> None:
    global FLASH_MIN_T
    FLASH_MIN_T = int(n)


# Optional activation sharding constraint (sequence parallelism for the 67B /
# 90B dry-runs: ghost records inherit it, bounding per-device record bytes).
_ACT_SPEC = None


def set_act_sharding(spec) -> None:
    global _ACT_SPEC
    _ACT_SPEC = spec


def maybe_shard(x):
    if _ACT_SPEC is not None and x.ndim == 3:
        return jax.lax.with_sharding_constraint(x, _ACT_SPEC)
    return x


# Expert-parallel constraint for MoE dispatch buffers (E, B, cap, D): without
# it GSPMD replicates the capacity buffers instead of sharding E over 'model'.
_EXPERT_SPEC = None


def set_expert_sharding(spec) -> None:
    global _EXPERT_SPEC
    _EXPERT_SPEC = spec


def maybe_shard_expert(x):
    if _EXPERT_SPEC is not None and x.ndim == 4:
        return jax.lax.with_sharding_constraint(x, _EXPERT_SPEC)
    return x


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    causal: bool = True
    window: int = 0          # 0 = full; >0 = sliding window (and ring cache)


def decode_positions(pos, B: int):
    """Normalise a decode position argument to a (B,) int32 vector.

    Scalar pos means the whole batch decodes in lockstep (the pre-serving
    contract); a (B,) vector gives every cache slot its own write position
    (continuous batching).
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.broadcast_to(pos, (B,))
    if pos.shape != (B,):
        raise ValueError(f"decode pos must be scalar or shape ({B},); "
                         f"got {pos.shape}")
    return pos


# ---------------------------------------------------------------------------
# chunked-prefill helpers (repro.serve: consume (B, C) tokens per fused call)
# ---------------------------------------------------------------------------

def chunk_valid(tokens, n_tok):
    """(B, C) bool mask: token j of each row is consumed iff j < n_tok[row].
    Rows may consume 0..C tokens; unconsumed tail tokens must leave the
    cache untouched (their writes are dropped / state updates masked)."""
    C = tokens.shape[1]
    return jnp.arange(C, dtype=jnp.int32)[None, :] < n_tok[:, None]


def gather_last(x, n_tok):
    """x (B, C, ...) -> (B, 1, ...): each row's entry at its LAST consumed
    chunk index n_tok-1 (clipped for n_tok == 0 rows, whose output is
    ignored by the scheduler)."""
    last = jnp.clip(n_tok - 1, 0, x.shape[1] - 1).astype(jnp.int32)
    idx = last.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.take_along_axis(x, idx, axis=1)


def scatter_rows(buf, slot, vals, valid):
    """Write vals (B, T, ...) into buf (B, S, ...) at per-token indices
    slot (B, T).  Invalid chunk-tail tokens are redirected out of bounds and
    dropped by the scatter — no second cache-sized select buffer."""
    B, T = slot.shape
    rows = jnp.broadcast_to(jnp.arange(B)[:, None], (B, T))
    if valid is not None:
        slot = jnp.where(valid, slot, buf.shape[1])
        return buf.at[rows, slot].set(vals.astype(buf.dtype), mode="drop")
    return buf.at[rows, slot].set(vals.astype(buf.dtype))


def attn_params(key, d_model: int, a: AttnCfg):
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_params(ks[0], d_model, a.n_heads * a.head_dim, a.qkv_bias),
        "wk": dense_params(ks[1], d_model, a.n_kv_heads * a.head_dim, a.qkv_bias),
        "wv": dense_params(ks[2], d_model, a.n_kv_heads * a.head_dim, a.qkv_bias),
        "wo": dense_params(ks[3], a.n_heads * a.head_dim, d_model),
    }
    if a.qk_norm:
        p["qn"] = norm_params(a.head_dim)
        p["kn"] = norm_params(a.head_dim)
    return p


def _sdpa(q, k, v, mask, scale: Optional[float] = None):
    """q (B,T,Hkv,G,Dh), k/v (B,S,Hkv,Dh), mask (B,T,S) or (T,S) bool;
    the scores take ``scale`` (default Dh^-1/2).

    Inputs stay in their storage dtype (bf16 on TPU); the MXU accumulates in
    f32 via preferred_element_type — no f32 copies of the KV cache."""
    scale = jnp.asarray(q.shape[-1] ** -0.5 if scale is None else scale,
                        q.dtype)
    s = jnp.einsum("btkgd,bskd->bktgs", q * scale, k,
                   preferred_element_type=jnp.float32)
    if mask.ndim == 2:
        m = mask[None, None, :, None, :]
    else:
        m = mask[:, None, :, None, :]
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bktgs,bskd->btkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(v.dtype)


def _qk_normalize(tape, scope, path, p, q, k, a: AttnCfg):
    if not a.qk_norm:
        return q, k

    def rn(nm, x, pp):
        xf = x.astype(jnp.float32)
        xhat = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
        return L.scale(tape, f"{scope}.{nm}", xhat.astype(x.dtype), pp["w"],
                       param_path=f"{path}.{nm}.w")
    return rn("qn", q, p["qn"]), rn("kn", k, p["kn"])


def attention(tape: Tape, scope: str, path: str, p, x, a: AttnCfg, *,
              positions=None, kv_x=None, cache: Optional[Dict] = None,
              pos=None, valid=None):
    """Self or cross attention.

    Training: positions (B,T) (or None for bidirectional), cache None.
    Decode: x (B,T,D) — T == 1 for plain decode, T == C for a chunked
    prefill step — cache {'k','v'} (B,S,Hkv,Dh); pos int32 start position —
    a scalar (whole batch in lockstep) or a (B,) vector of per-sequence
    positions (continuous batching: each cache slot holds an independent
    request at its own depth); token i of a chunk lands at pos+i.  valid
    (B,T) masks unconsumed chunk-tail tokens: their KV writes are dropped
    and their outputs are garbage the caller ignores.
    Returns (out, new_cache).
    """
    B, T, _ = x.shape
    H, Hkv, Dh = a.n_heads, a.n_kv_heads, a.head_dim
    G = H // Hkv

    def proj(nm, src_):
        return L.dense(tape, f"{scope}.{nm}", src_, p[nm]["w"], p[nm].get("b"),
                       param_path=f"{path}.{nm}").reshape(
            B, src_.shape[1], -1, Dh)

    q = proj("wq", x)
    new_cache = cache

    if cache is not None and "xk" in cache:
        # cross attention against precomputed (cached) encoder/image KV
        k, v = cache["xk"], cache["xv"]
        mask = jnp.ones((T, k.shape[1]), bool)
        o = _sdpa(q.reshape(B, T, Hkv, G, Dh), k, v, mask)
    elif kv_x is not None:
        # cross attention, KV projected from the encoder stream
        k, v = proj("wk", kv_x), proj("wv", kv_x)
        mask = jnp.ones((T, k.shape[1]), bool)
        o = _sdpa(q.reshape(B, T, Hkv, G, Dh), k, v, mask)
    elif cache is not None:
        # decode self-attention: project the chunk's T tokens (T == 1 for
        # plain decode) and write them at per-slot offsets pos..pos+T-1 in
        # one scatter; within-chunk causality falls out of the read mask
        # (token i sees cache rows <= pos+i, which includes tokens j <= i of
        # its own chunk — written by the same scatter — and nothing later)
        posb = decode_positions(pos, B)                    # (B,) int32
        if a.window and T > 1:
            # a ring cache cannot take a single-scatter chunk: once a slot's
            # positions wrap the window, a later in-chunk token's write lands
            # on the ring row an earlier in-chunk token must still read, and
            # the read mask's position reconstruction then attends to the
            # NEW key under the old position — silently wrong tokens.
            # Sliding-window archs serve with prefill_chunk=1 (also enforced
            # at ServeEngine construction).
            raise ValueError(
                f"chunked prefill (T={T}) is unsupported on sliding-window "
                f"attention (window={a.window}): in-chunk ring writes "
                f"overwrite rows earlier chunk tokens still read once "
                f"positions wrap; serve this arch with prefill_chunk=1")
        k1, v1 = proj("wk", x), proj("wv", x)
        q, k1 = _qk_normalize(tape, scope, path, p, q, k1, a)
        tok_pos = posb[:, None] + jnp.arange(T, dtype=jnp.int32)   # (B,T)
        if a.use_rope:
            q = apply_rope(q, tok_pos, a.rope_theta)
            k1 = apply_rope(k1, tok_pos, a.rope_theta)
        S = cache["k"].shape[1]
        slot = (tok_pos % S) if a.window else tok_pos      # (B,T)
        new_cache = dict(cache)
        ck = scatter_rows(cache["k"], slot, k1, valid)
        cv = scatter_rows(cache["v"], slot, v1, valid)
        new_cache["k"], new_cache["v"] = ck, cv
        sl = jnp.arange(S)[None, None, :]                  # (1,1,S)
        pc = tok_pos[:, :, None]                           # (B,T,1)
        if a.window:
            orig = pc - jnp.mod(pc - sl, S)     # original position in ring slot
            mask = (orig >= 0) & (orig <= pc) & (orig > pc - a.window)
        else:
            mask = sl <= pc                                # (B,T,S)
        o = _sdpa(q.reshape(B, T, Hkv, G, Dh), ck, cv, mask)
    else:
        # full-sequence self attention (training / prefill)
        k, v = proj("wk", x), proj("wv", x)
        q, k = _qk_normalize(tape, scope, path, p, q, k, a)
        if a.use_rope and positions is not None:
            q = apply_rope(q, positions, a.rope_theta)
            k = apply_rope(k, positions, a.rope_theta)
        S = k.shape[1]
        if a.causal and T >= FLASH_MIN_T:
            from .flashattn import flash_sdpa
            o = flash_sdpa(q.reshape(B, T, Hkv, G, Dh), k, v,
                           causal=True, window=a.window)
        else:
            ti = jnp.arange(T)[:, None]
            si = jnp.arange(S)[None, :]
            if a.causal:
                mask = si <= ti
                if a.window:
                    mask = mask & (si > ti - a.window)
            else:
                mask = jnp.ones((T, S), bool)
            o = _sdpa(q.reshape(B, T, Hkv, G, Dh), k, v, mask)

    o = o.reshape(B, T, H * Dh)
    out = L.dense(tape, f"{scope}.wo", o, p["wo"]["w"], None,
                  param_path=f"{path}.wo")
    return out, new_cache


def cross_kv(tape: Tape, scope: str, path: str, p, kv_x, a: AttnCfg):
    """Precompute cross-attention K/V from an encoder stream (cache init)."""
    B = kv_x.shape[0]
    k = L.dense(tape, f"{scope}.wk", kv_x, p["wk"]["w"], p["wk"].get("b"),
                param_path=f"{path}.wk").reshape(B, kv_x.shape[1], -1, a.head_dim)
    v = L.dense(tape, f"{scope}.wv", kv_x, p["wv"]["w"], p["wv"].get("b"),
                param_path=f"{path}.wv").reshape(B, kv_x.shape[1], -1, a.head_dim)
    return k, v


def init_attn_cache(B, S, a: AttnCfg, dtype=jnp.bfloat16):
    size = a.window if a.window else S
    return {"k": jnp.zeros((B, size, a.n_kv_heads, a.head_dim), dtype),
            "v": jnp.zeros((B, size, a.n_kv_heads, a.head_dim), dtype)}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_params(key, d_model, d_ff):
    ks = jax.random.split(key, 3)
    return {"w1": dense_params(ks[0], d_model, d_ff),
            "w3": dense_params(ks[1], d_model, d_ff),
            "w2": dense_params(ks[2], d_ff, d_model)}


def swiglu(tape: Tape, scope: str, path: str, p, x):
    g = L.dense(tape, f"{scope}.w1", x, p["w1"]["w"], param_path=f"{path}.w1")
    u = L.dense(tape, f"{scope}.w3", x, p["w3"]["w"], param_path=f"{path}.w3")
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return L.dense(tape, f"{scope}.w2", h, p["w2"]["w"], param_path=f"{path}.w2")


def gelu_mlp_params(key, d_model, d_ff):
    ks = jax.random.split(key, 2)
    return {"w1": dense_params(ks[0], d_model, d_ff, use_bias=True),
            "w2": dense_params(ks[1], d_ff, d_model, use_bias=True)}


def gelu_mlp(tape: Tape, scope: str, path: str, p, x):
    h = L.dense(tape, f"{scope}.w1", x, p["w1"]["w"], p["w1"]["b"],
                param_path=f"{path}.w1")
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return L.dense(tape, f"{scope}.w2", h, p["w2"]["w"], p["w2"]["b"],
                   param_path=f"{path}.w2")


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def lm_head_ce(tape: Tape, head_p, x, labels, cfg, *, path: str = "head"):
    """Final head matmul + per-example CE, optionally chunked over T.

    Chunking never materialises the full (B,T,V) logits: the head dense runs
    per T-chunk inside a scan, registered under ``shared/`` so the clipping
    engines fold the chunk axis as exact parameter re-use.
    """
    from ..core.tape import scan_blocks
    B, T, D = x.shape
    ck = cfg.ce_chunk
    if not ck or T % ck or T <= ck:
        logits = L.dense(tape, "head", x, head_p["w"], param_path=path)
        return per_example_ce(logits, labels)

    nc = T // ck
    xc = x.reshape(B, nc, ck, D).transpose(1, 0, 2, 3)        # (nc,B,ck,D)
    lc = labels.reshape(B, nc, ck).transpose(1, 0, 2)         # (nc,B,ck)

    def body(sub, xs, acc):
        xchunk, lchunk = xs
        logits = L.dense(sub, "shared/head", xchunk, head_p["w"],
                         param_path=path)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        ll = jnp.take_along_axis(logp, lchunk[..., None], -1)[..., 0]
        return acc - ll.sum(axis=-1)

    acc = scan_blocks(tape, "cechunks", body, (xc, lc),
                      jnp.zeros(B, jnp.float32), nc)
    return acc / T


def per_example_ce(logits, labels, weights=None):
    """logits (B,T,V), labels (B,T) -> (B,) mean CE per example."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if weights is None:
        return -ll.mean(axis=-1)
    w = weights.astype(jnp.float32)
    return -(ll * w).sum(axis=-1) / jnp.maximum(w.sum(axis=-1), 1.0)


def per_example_ce_single(logits, labels):
    """logits (B,V), labels (B,) -> (B,)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
