"""Model FLOPs of one example: the operations the forward and the backward
pass require, 3 x (the forward's matmuls + attention's scores and
context).  Lookups (the embedding), the norm pass of ghost clipping, the
second backward and recomputation do not count.

The matmul count is the one ``repro.launch.costmodel._dense_fwd_flops``
makes, without the norms' gains, which it counts as matmuls once they are
stacked over layers, and with every row a leaf really multiplies: a ViT's patch embedding
sees the patches, its head the class token; a decoder's head sees every
position.  Attention counts the full T x T scores, causal or not, as
``costmodel._attn_fwd_flops`` does."""
from __future__ import annotations

import math

NOT_MATMUL = ("emb", "pos", "cls")


def _rows(path: str, model: dict, tokens: int) -> int:
    if model["family"] == "vit":
        if path.startswith("patch."):
            return tokens - 1
        if path.startswith("head."):
            return 1
    return tokens


def tokens_per_example(model: dict, seq_len: int) -> int:
    if model["family"] == "vit":
        return (model["image_size"] // model["patch"]) ** 2 + 1
    return seq_len


def per_example(model: dict, leaves: dict, seq_len: int) -> float:
    """``leaves``: dotted path -> shape of every parameter leaf."""
    T = tokens_per_example(model, seq_len)
    f = 0.0
    for path, shape in leaves.items():
        # a leaf under ``blocks`` carries the layer axis in front
        stacked = 1 if path.startswith("blocks.") else 0
        if (len(shape) - stacked < 2 or not path.endswith(".w")
                or path.split(".")[0] in NOT_MATMUL):
            continue
        stack = math.prod(shape[:-2])
        f += 2.0 * stack * _rows(path, model, T) * shape[-2] * shape[-1]
    hd = model.get("head_dim") or model["d_model"] // model["n_heads"]
    f += 4.0 * model["n_layers"] * T * T * model["n_heads"] * hd
    return 3.0 * f
