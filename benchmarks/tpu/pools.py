"""Inputs made from the seed: the weights and the data pool of a cell.

Both are the benchmark's own, so the plain reference can rebuild them from
the seed without taking anything the program under test made.

* Weights: one jitted call on the device, leaf by leaf from the shapes the
  program's parameter tree has, each leaf drawn by the first rule of the
  configuration's ``init`` table whose pattern matches its dotted path.
* Data: a host-resident pool drawn in bulk; :class:`PoolDataset` gathers
  rows by index modulo the pool size, which is what ``fit`` receives.
"""
from __future__ import annotations

import re

import numpy as np

SEED_WORDS = (1 << 32) - 1


def leaf_paths(tree, prefix=""):
    """Dotted paths of a nested-dict tree, in ``jax.tree`` leaf order."""
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out += leaf_paths(tree[k], f"{prefix}{k}.")
        return out
    return [prefix[:-1]]


def _rule(path, init):
    for pattern, kind, scale in init:
        if re.search(pattern, path):
            return kind, float(scale)
    raise ValueError(f"no init rule of the configuration matches {path!r}")


def weight_key(seed: int):
    import jax
    key = jax.random.PRNGKey(seed & SEED_WORDS)
    return jax.random.fold_in(key, (seed >> 32) & SEED_WORDS)


def make_weights(shapes, init, seed: int, out_shardings=None):
    """The cell's float32 weights, from the seed, in one jitted call.

    ``shapes`` is the parameter tree of ``jax.ShapeDtypeStruct`` (from
    ``jax.eval_shape`` of the model's init); a stacked leaf (layers first)
    is drawn whole, with its fan-in read from the second-to-last axis."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(shapes)
    paths = leaf_paths(shapes)
    rules = [_rule(p, init) for p in paths]

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, leaf, (kind, scale) in zip(keys, leaves, rules):
            shape = leaf.shape
            if kind == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            elif kind == "zeros":
                out.append(jnp.zeros(shape, jnp.float32))
            elif kind == "normal":
                out.append(jax.random.normal(k, shape, jnp.float32) * scale)
            elif kind == "fan_in":
                out.append(jax.random.normal(k, shape, jnp.float32)
                           * shape[-2] ** -0.5)
            else:
                raise ValueError(f"unknown init kind {kind!r}")
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build, out_shardings=out_shardings)(weight_key(seed))


class PoolDataset:
    """A pool of rows gathered by index modulo its size.

    ``fetch(idx)`` is the callable ``fit`` feeds its memory manager with.
    While ``record`` is a list, every index array asked for is appended to
    it (the check reads which examples the sampler drew); while
    ``annotate`` is set, each fetch is a profiler span."""

    def __init__(self, n: int, arrays: dict):
        self.n = n
        self.arrays = arrays
        self.size = len(next(iter(arrays.values())))
        self.record = None
        self.annotate = False

    def fetch(self, idx):
        idx = np.asarray(idx)
        if self.record is not None:
            self.record.append(idx.copy())
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation("input/fetch"):
                return self.rows(idx)
        return self.rows(idx)

    def rows(self, idx):
        """The pool rows behind dataset indices, without recording."""
        rows = np.asarray(idx) % self.size
        return {k: v[rows] for k, v in self.arrays.items()}


def make_pool(pool: dict, model: dict, seq_len: int, n: int,
              seed: int) -> PoolDataset:
    """The cell's data pool from its ``pool`` parameters and the seed."""
    import ml_dtypes
    rng = np.random.default_rng([seed & SEED_WORDS, seed >> 32, 7])
    size = int(pool["size"])
    if pool["kind"] == "images":
        s = model["image_size"]
        images = rng.standard_normal((size, s, s, 3), np.float32)
        labels = rng.integers(0, model["n_classes"], size, dtype=np.int32)
        return PoolDataset(n, {"image": images.astype(ml_dtypes.bfloat16),
                               "label": labels})
    if pool["kind"] == "tokens":
        toks = rng.integers(0, model["vocab"], (size, seq_len + 1),
                            dtype=np.int32)
        return PoolDataset(n, {"tokens": np.ascontiguousarray(toks[:, :-1]),
                               "labels": np.ascontiguousarray(toks[:, 1:])})
    raise ValueError(f"unknown pool kind {pool['kind']!r}")
