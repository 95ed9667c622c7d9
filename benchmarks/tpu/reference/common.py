"""Pieces the plain models share: matmuls that can be put at a lower
precision (the control), norms, and the loss."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int8(x):
    """Symmetric per-tensor int8 rounding, passed straight through in the
    backward pass: the control's matmul operands."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


class Numerics:
    """How the reference multiplies: float32 at "highest", or, for the
    control, with both operands of every matmul rounded to int8."""

    def __init__(self, int8: bool = False):
        self.int8 = int8

    def mm(self, spec, a, b):
        if self.int8:
            a, b = quantize_int8(a), quantize_int8(b)
        return jnp.einsum(spec, a, b,
                          precision=jax.lax.Precision.HIGHEST)


def layernorm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def rmsnorm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def cross_entropy(logits, labels):
    """Mean negative log-likelihood over the trailing positions."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
