"""The clipped gradient sum of one logical batch, in blocks of examples.

DP engines: each example's gradient over all parameters, scaled by
min(1, C / its norm), summed.  Non-private: the plain gradient of the summed
loss.  Both in float32 at "highest" matmul precision (or the control's
int8), one jitted block at a time so that a block of per-example gradients
fits beside the weights."""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from .common import Numerics


def model_loss(name: str):
    return importlib.import_module(f"{__package__}.{name}").loss


def _pad(rows: dict, n: int):
    """Rows padded to n by repeating row 0, and the 0/1 weights."""
    have = len(next(iter(rows.values())))
    w = np.zeros(n, np.float32)
    w[:have] = 1.0
    if have == n:
        return rows, w
    idx = np.concatenate([np.arange(have), np.zeros(n - have, np.int64)])
    return {k: v[idx] for k, v in rows.items()}, w


class ClippedSum:
    """``self(params, rows)`` -> the summed (clipped) gradient tree."""

    def __init__(self, reference: str, model: dict, *, private: bool,
                 clip_norm: float, block: int, int8: bool = False):
        loss = model_loss(reference)
        num = Numerics(int8=int8)
        self.block = block

        def one(params, ex):
            batch = jax.tree.map(lambda x: x[None], ex)
            return loss(params, batch, model, num)[0]

        def clipped_block(params, rows, w):
            g = jax.vmap(jax.grad(one), in_axes=(None, 0))(params, rows)
            sq = sum(jnp.sum(x.reshape(x.shape[0], -1) ** 2, axis=1)
                     for x in jax.tree.leaves(g))
            coef = w * jnp.minimum(1.0, clip_norm / jnp.sqrt(sq))
            return jax.tree.map(
                lambda x: jnp.tensordot(coef, x, axes=1,
                                        precision="highest"), g)

        def plain_block(params, rows, w):
            def total(p):
                return jnp.sum(w * loss(p, rows, model, num))
            return jax.grad(total)(params)

        self._block = jax.jit(clipped_block if private else plain_block)

    def __call__(self, params, rows: dict):
        n = len(next(iter(rows.values())))
        acc = None
        for s in range(0, n, self.block):
            part = {k: v[s:s + self.block] for k, v in rows.items()}
            part, w = _pad(part, self.block)
            part = jax.tree.map(
                lambda x: jnp.asarray(x, jnp.float32)
                if x.dtype.kind == "f" or x.dtype.kind == "V" else
                jnp.asarray(x), part)
            g = self._block(params, part, jnp.asarray(w))
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
        return acc
