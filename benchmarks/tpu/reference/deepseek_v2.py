"""DeepSeek-V2 decoder (arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite)
in plain float32, for a chip's share of the routed experts.

* Multi-head latent attention without q compression: q = h Wq split into
  (nope, rope) parts per head; the latent c = RMSNorm(h Wdkv) gives k_nope
  and v through Wukv; one rope key h Wkr is shared by every head.  Rotary
  positions are YaRN-scaled (below) and rotate in the half-split form (see
  the configuration's ``departures``).  Scores are q_nope.k_nope +
  q_rope.k_rope times (nope + rope)^-1/2 x mscale(factor, mscale_all_dim)^2,
  causal.
* YaRN (arXiv:2309.00071, DeepSeek-V2's form): pair i of the rope dims
  turns L0 theta^(-2i/d) / (2 pi) times over the original context L0; the
  pairs below the one that turns beta_fast times keep their frequency,
  those above the one that turns beta_slow times are divided by the
  factor, and a linear ramp joins them.  cos and sin take mscale(factor,
  mscale) / mscale(factor, mscale_all_dim), with mscale(s, m) =
  0.1 m ln s + 1.
* The first ``first_dense_layers`` layers have a SwiGLU MLP; the others a
  mixture of experts: a float32 softmax gate over all ``n_router_experts``
  experts, the greedy top-k without renormalising the gates, the shared
  experts' SwiGLU (width n_shared x the expert width) on every token, and
  the held experts [offset, offset + n_experts).  Each held expert is
  evaluated on every token and weighted by gate x keep, where keep says the
  token is among the first ceil(T K cf / n_router_experts) tokens of its
  sequence to choose the expert (an exclusive running count).
* The per-example loss is the mean next-token cross entropy over the
  positions plus, for each expert layer, alpha x E x sum_i f_i P_i over all
  E router experts, f_i the share of the row's T K choices that picked
  expert i and P_i its mean gate probability (``seq_aux``).

qk_nope_head_dim and v_head_dim are the configuration's ``head_dim``
(128 published, at most 128 in the program)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import cross_entropy, rmsnorm

NORM_EPS = 1e-6


def yarn(dim: int, model: dict):
    """(inverse frequencies of the dim/2 rope pairs, cos/sin factor,
    softmax-scale factor)."""
    base = model["rope_theta"]
    inv = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    s = model.get("rope_scaling_factor", 0.0)
    if not s:
        return inv, 1.0, 1.0
    orig = model["rope_original_max_pos"]

    def pair(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(pair(model["rope_beta_fast"])), 0)
    high = min(math.ceil(pair(model["rope_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = inv * (1.0 - ramp) + inv / s * ramp

    def mscale(m):
        return 0.1 * m * math.log(s) + 1.0 if s > 1 else 1.0
    all_dim = model.get("rope_mscale_all_dim", 0.0)
    score = mscale(all_dim) ** 2 if all_dim else 1.0
    return inv, mscale(model["rope_mscale"]) / mscale(all_dim), score


def _rope(x, inv, factor):
    """x (B, T, ..., d): half-split rotation at positions 0..T-1."""
    T = x.shape[1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    shape = (1, T) + (1,) * (x.ndim - 3) + (inv.shape[0],)
    cos = (jnp.cos(ang) * factor).reshape(shape)
    sin = (jnp.sin(ang) * factor).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, m, num):
    g = num.mm("btd,df->btf", h, m["w1"]["w"])
    u = num.mm("btd,df->btf", h, m["w3"]["w"])
    return num.mm("btf,fd->btd", jax.nn.silu(g) * u, m["w2"]["w"])


def _attention(h, a, model, num):
    B, T, _ = h.shape
    H, dn = model["n_heads"], model["head_dim"]
    rd = model["rope_dim"]
    inv, factor, score = yarn(rd, model)
    q = num.mm("btd,de->bte", h, a["wq"]["w"]).reshape(B, T, H, dn + rd)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], inv, factor)
    c = rmsnorm(num.mm("btd,dr->btr", h, a["wdkv"]["w"]),
                a["ckv_norm"]["w"], NORM_EPS)
    kv = num.mm("btr,re->bte", c, a["wukv"]["w"]).reshape(B, T, H, 2 * dn)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k_rope = _rope(num.mm("btd,dr->btr", h, a["wkr"]["w"]), inv, factor)
    s = (num.mm("bthd,bshd->bhts", q_nope, k_nope)
         + num.mm("bthr,bsr->bhts", q_rope, k_rope))
    s = s * ((dn + rd) ** -0.5 * score)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = num.mm("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    return num.mm("bte,ed->btd", o.reshape(B, T, H * dn), a["wo"]["w"])


def _experts(h, m, model, num):
    """(the held experts' part of the routed output, the aux term)."""
    T = h.shape[1]
    E, K = model["n_experts"], model["top_k"]
    ER = model.get("n_router_experts") or E
    off = model.get("expert_offset", 0)
    cap = math.ceil(T * K * model["capacity_factor"] / ER)
    probs = jax.nn.softmax(
        num.mm("btd,de->bte", h, m["router"]["w"]), axis=-1)      # (B,T,ER)
    topv, topi = jax.lax.top_k(probs, K)                           # (B,T,K)
    picked = jax.nn.one_hot(topi, ER, dtype=jnp.float32)           # (B,T,K,ER)
    gate = jnp.sum(topv[..., None] * picked, axis=2)                # 0 unless chosen
    chose = picked.sum(axis=2)                                      # (B,T,ER)
    earlier = jnp.cumsum(chose, axis=1) - chose
    keep = (chose > 0) & (earlier < cap)
    y = 0.0
    for e in range(E):
        g = num.mm("btd,df->btf", h, m["w1"]["w"][e])
        u = num.mm("btd,df->btf", h, m["w3"]["w"][e])
        out = num.mm("btf,fd->btd", jax.nn.silu(g) * u, m["w2"]["w"][e])
        w = jnp.where(keep[..., off + e], gate[..., off + e], 0.0)
        y = y + w[..., None] * out
    f = chose.sum(axis=1) / (T * K)                                 # (B,ER)
    aux = model["router_aux_coef"] * ER * jnp.sum(f * probs.mean(axis=1), -1)
    return y, aux


def loss(params, batch, model: dict, num):
    """Per-example loss, (B,)."""
    x = params["emb"]["w"][batch["tokens"]]
    aux = 0.0
    for name in ("dense_blocks", "moe_blocks"):
        blocks = params[name]
        n = jax.tree.leaves(blocks)[0].shape[0]
        for l in range(n):
            blk = jax.tree.map(lambda a, l=l: a[l], blocks)
            h = rmsnorm(x, blk["ln1"]["w"], NORM_EPS)
            x = x + _attention(h, blk["attn"], model, num)
            h = rmsnorm(x, blk["ln2"]["w"], NORM_EPS)
            if name == "dense_blocks":
                x = x + _swiglu(h, blk["mlp"], num)
            else:
                routed, a = _experts(h, blk["moe"], model, num)
                x = x + routed + _swiglu(h, blk["shared"], num)
                aux = aux + a
    x = rmsnorm(x, params["lnf"]["w"], NORM_EPS)
    logits = num.mm("btd,dv->btv", x, params["head"]["w"])
    return cross_entropy(logits, batch["labels"]).mean(-1) + aux
