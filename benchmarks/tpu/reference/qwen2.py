"""Qwen2 decoder (arXiv:2407.10671; hf Qwen/Qwen2-0.5B) in plain float32:
token embedding, pre-norm blocks of RMSNorm, grouped-query causal
attention with bias on q, k and v and rotary positions (half-split
rotation, theta from the configuration), a SwiGLU MLP, a final RMSNorm and
a linear head (untied: see the configuration's ``departures``).  The loss
of a sequence is the mean next-token cross entropy over its positions."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import cross_entropy, rmsnorm

NORM_EPS = 1e-6


def _rope(x, theta):
    T, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(params, batch, model: dict, num):
    """Per-example mean next-token cross entropy, (B,)."""
    tokens = batch["tokens"]
    B, T = tokens.shape
    H, Hkv = model["n_heads"], model["n_kv_heads"]
    x = params["emb"]["w"][tokens]
    D = x.shape[-1]
    dh, G = D // H, H // Hkv
    causal = jnp.tril(jnp.ones((T, T), bool))
    blocks = params["blocks"]
    for l in range(model["n_layers"]):
        blk = jax.tree.map(lambda a, l=l: a[l], blocks)
        a = blk["attn"]
        h = rmsnorm(x, blk["ln1"]["w"], NORM_EPS)

        def proj(name, heads, h=h, a=a):
            y = num.mm("btd,de->bte", h, a[name]["w"]) + a[name]["b"]
            return y.reshape(B, T, heads, dh)

        q = _rope(proj("wq", H), model["rope_theta"])
        k = _rope(proj("wk", Hkv), model["rope_theta"])
        v = proj("wv", Hkv)
        q = q.reshape(B, T, Hkv, G, dh)
        s = num.mm("btkgd,bskd->bkgts", q, k) / jnp.sqrt(float(dh))
        s = jnp.where(causal, s, -jnp.inf)
        o = num.mm("bkgts,bskd->btkgd", jax.nn.softmax(s, axis=-1), v)
        x = x + num.mm("bte,ed->btd", o.reshape(B, T, D), a["wo"]["w"])
        h = rmsnorm(x, blk["ln2"]["w"], NORM_EPS)
        m = blk["mlp"]
        g = num.mm("btd,df->btf", h, m["w1"]["w"])
        u = num.mm("btd,df->btf", h, m["w3"]["w"])
        x = x + num.mm("btf,fd->btd", jax.nn.silu(g) * u, m["w2"]["w"])
    x = rmsnorm(x, params["lnf"]["w"], NORM_EPS)
    logits = num.mm("btd,dv->btv", x, params["head"]["w"])
    return cross_entropy(logits, batch["labels"]).mean(-1)
