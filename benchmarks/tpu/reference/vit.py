"""ViT-B/16 classifier (Dosovitskiy et al. 2021, arXiv:2010.11929) in
plain float32: a linear patch embedding, a class token, learned positions,
pre-norm blocks of multi-head self-attention and a GELU MLP, a final
LayerNorm and a linear head on the class token.  GELU is the tanh
approximation (see the configuration's ``departures``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import cross_entropy, layernorm

LN_EPS = 1e-5


def loss(params, batch, model: dict, num):
    """Per-example cross entropy, (B,)."""
    p, heads = model["patch"], model["n_heads"]
    x = batch["image"].astype(jnp.float32)
    B, S = x.shape[0], x.shape[1]
    n = S // p
    x = x.reshape(B, n, p, n, p, 3).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B, n * n, p * p * 3)
    x = num.mm("bti,io->bto", x, params["patch"]["w"]) + params["patch"]["b"]
    D = x.shape[-1]
    cls = jnp.broadcast_to(params["cls"]["w"], (B, 1, D))
    x = jnp.concatenate([cls, x], axis=1) + params["pos"]["w"]
    blocks = params["blocks"]
    for l in range(model["n_layers"]):
        blk = jax.tree.map(lambda a, l=l: a[l], blocks)
        h = layernorm(x, blk["ln1"]["g"]["w"], blk["ln1"]["b"]["w"], LN_EPS)
        a = blk["attn"]
        T, dh = h.shape[1], D // heads

        def proj(name, h=h, a=a):
            y = num.mm("btd,de->bte", h, a[name]["w"]) + a[name]["b"]
            return y.reshape(B, T, heads, dh)

        q, k, v = proj("wq"), proj("wk"), proj("wv")
        s = num.mm("bthd,bshd->bhts", q, k) / jnp.sqrt(float(dh))
        o = num.mm("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
        x = x + num.mm("btd,de->bte", o.reshape(B, T, D), a["wo"]["w"])
        h = layernorm(x, blk["ln2"]["g"]["w"], blk["ln2"]["b"]["w"], LN_EPS)
        m = blk["mlp"]
        h = jax.nn.gelu(num.mm("btd,df->btf", h, m["w1"]["w"]) + m["w1"]["b"])
        x = x + num.mm("btf,fd->btd", h, m["w2"]["w"]) + m["w2"]["b"]
    x = layernorm(x, params["lnf"]["g"]["w"], params["lnf"]["b"]["w"], LN_EPS)
    logits = num.mm("bd,dc->bc", x[:, 0], params["head"]["w"]) \
        + params["head"]["b"]
    return cross_entropy(logits, batch["label"])
