"""Jit'd pytree-level wrappers around the Pallas kernels.

These are the integration points the DP step builders swap in:
  * ``tree_clip_accum``    — replaces the clip+accumulate of the pe engines.
  * ``flat_clip_accum``    — the streaming engine's tile accumulate: an
                             m-row per-example tile clipped and added into
                             the flat accumulator IN PLACE (aliased
                             input/output), clip declared on the result.
  * ``tree_noisy_update``  — the fused noise + SGD(+momentum) apply over the
                             flat gradient accumulator (one read+write of
                             params/acc/momentum per step).
  * ``ghost_norm_dense``   — drop-in for the dense direct-path norm.

``tree_noisy_update`` has two executions of the same math, chosen by
``use_kernel`` (default: the Pallas kernel on TPU, pure XLA elsewhere):

  * kernel  — one :func:`~repro.kernels.noisy_update.noisy_sgd_update` call
              per parameter leaf against its static offset range of the flat
              accumulator; on TPU the noise is drawn in-kernel (``seed=``)
              so the noise buffer never round-trips HBM.
  * XLA     — the identical flat expression written so XLA's fusion produces
              one loop per leaf over (params, acc segment, momentum segment):
              static slices of the flat buffers fuse into their consumers,
              which is what the step-phase benchmark's bytes-accessed
              assertion pins down structurally.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..analysis.marks import mark as dp_mark
from ..utils.params import FlatGradView
from .clip_accum import clip_accum, clip_accum_inplace
from .ghost_norm import ghost_norm_dense  # re-export
from .noisy_update import noisy_sgd_update

__all__ = ["clip_accum", "flat_clip_accum", "ghost_norm_dense",
           "interpret_mode", "noisy_sgd_update", "tree_clip_accum",
           "tree_noisy_update"]


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted: exactly when the default
    backend is not a TPU.  The one place that decision is made — on a TPU
    every kernel compiles through Mosaic, and off it the interpreter runs
    the same kernel body as XLA ops."""
    return jax.default_backend() != "tpu"


def tree_clip_accum(per_example_grads, norms, mask, clip_norm, *,
                    interpret: bool):
    """per_example_grads: pytree with leading B axis -> clipped masked sum."""
    leaves, treedef = jax.tree.flatten(per_example_grads)
    B = leaves[0].shape[0]
    # keep the storage dtype (bf16 under pe_bf16): the kernel upcasts per
    # VMEM tile, so no full f32 HBM copy is materialised here
    flat = jnp.concatenate([l.reshape(B, -1) for l in leaves], axis=1)
    summed = clip_accum(flat, norms, mask, clip_norm, interpret=interpret)
    # the kernel clips AND sums over the example axis internally — declare
    # both to the static verifier (aggregated=True discharges the batch axis
    # the opaque pallas_call otherwise taints conservatively)
    summed = dp_mark("clip", summed, aggregated=True)
    out, off = [], 0
    for l in leaves:
        sz = int(l.size) // B
        out.append(summed[off:off + sz].reshape(l.shape[1:]))
        off += sz
    return jax.tree.unflatten(treedef, out)


def flat_clip_accum(acc, tile_grads, norms, mask, clip_norm, *,
                    interpret: bool, tile_d=None):
    """Streaming accumulate: ``acc (D,) += Σ_b coef_b · tile_grads[b]``.

    ``tile_grads`` is an (m, D) per-example tile already in the flat
    accumulator layout (zero over the alignment tail); ``acc`` is passed as
    an aliased operand and updated in place.  The kernel clips AND sums over
    the tile's example axis internally, so — exactly like
    :func:`tree_clip_accum` — the result is declared a clip site with the
    batch axis discharged (``aggregated=True``): the opaque pallas_call
    would otherwise taint every output dim conservatively."""
    out = clip_accum_inplace(acc, tile_grads, norms, mask, clip_norm,
                             interpret=interpret, tile_d=tile_d)
    return dp_mark("clip", out, aggregated=True)


def tree_noisy_update(params, grad_acc, key, sigma_c, expected_batch, lr, *,
                      momentum_buf=None, momentum=0.0,
                      view: Optional[FlatGradView] = None,
                      use_kernel: Optional[bool] = None,
                      interpret: Optional[bool] = None,
                      in_kernel_rng: Optional[bool] = None,
                      kernel_map=None):
    """Fused DP-SGD apply: params tree + flat accumulator -> new params tree.

    ``grad_acc`` is the flat f32 accumulator laid out by ``view`` (built from
    ``params`` when omitted; a legacy pytree accumulator is flattened first).
    ``momentum_buf``, when given, is the flat momentum buffer and a
    ``(new_params, new_momentum)`` pair is returned.  ``key=None`` skips the
    noise term entirely (``sigma_c`` is then ignored — the non-private fused
    step), in which case ``expected_batch`` may be a traced scalar (the seen
    count).

    ``in_kernel_rng`` forces the noise source on the kernel path: ``True``
    draws inside the kernel (hardware PRNG on TPU, the threefry fallback in
    interpret mode), ``False`` precomputes the flat ``view.noise`` operand.
    The default (``None``) keeps the historical choice — in-kernel on real
    TPU, noise-operand everywhere else, so off-TPU callers keep sharing one
    ``view.noise`` stream with the generic path.

    ``kernel_map`` (``ShardingConstraints.kernel_map``) runs each kernel
    call on every device of a mesh over the replicated buffers: a Mosaic
    kernel compiles under a mesh only that way.  Every device draws the
    same in-kernel noise from the same seed, so the replicas stay equal.
    """
    if view is None:
        view = FlatGradView.for_tree(params)
    if not (hasattr(grad_acc, "ndim") and grad_acc.ndim == 1):
        grad_acc = view.flatten(grad_acc)          # legacy pytree accumulator
    use_kernel = (not interpret_mode()) if use_kernel is None else use_kernel
    interpret = interpret_mode() if interpret is None else interpret
    leaves = jax.tree.leaves(params)

    # static sigma*C (the usual case: DPConfig floats) is declared on the
    # noise mark so the verifier can check it against the accountant
    scale = float(sigma_c) if isinstance(sigma_c, (int, float)) else None

    if use_kernel:
        if in_kernel_rng is None:
            in_kernel_rng = not interpret
        in_kernel_rng = key is not None and in_kernel_rng
        z = (None if key is None or in_kernel_rng else view.noise(key))
        if z is not None:
            z = dp_mark("noise", z, scale=scale)
        if in_kernel_rng:
            kd = (key if jnp.issubdtype(key.dtype, jnp.unsignedinteger)
                  else jax.random.key_data(key))     # old- vs new-style keys
            seeds = kd.astype(jnp.uint32).reshape(-1)[-2:]
        else:
            seeds = None
        def leaf_update(p, a, z, m, seed, sc, denom, lr_):
            return noisy_sgd_update(p, a, z, sc, denom, lr_, momentum_buf=m,
                                    momentum=momentum, seed=seed,
                                    interpret=interpret)
        sc, denom, lr_ = (sigma_c if key is not None else 0.0,
                          expected_batch, lr)
        if kernel_map is not None:
            leaf_update = kernel_map(leaf_update)
            # shard_map passes arrays only
            sc, denom, lr_ = (jnp.asarray(x, jnp.float32)
                              for x in (sc, denom, lr_))

        def seg(buf, o, n):
            return None if buf is None else jax.lax.slice(buf, (o,), (o + n,))

        newp, newm_segs = [], []
        for i, p in enumerate(leaves):
            o, n = view.offsets[i], view.sizes[i]
            # fold the leaf index into the seed: leaves get independent
            # in-kernel streams (program_id only separates tiles).  key=None
            # leaves noise AND seed unset -> the kernel's noiseless variants
            # (no zero buffer is materialised or read)
            seed = seeds + jnp.uint32(i) if in_kernel_rng else None
            out = leaf_update(p.reshape(-1).astype(jnp.float32),
                              seg(grad_acc, o, n), seg(z, o, n),
                              seg(momentum_buf, o, n), seed, sc, denom, lr_)
            if momentum_buf is not None:
                out, newm = out
                newm_segs.append(newm)
            if in_kernel_rng:
                # the draw happens inside the kernel: declare it on the
                # kernel's output, one mark per disjoint leaf segment
                out = dp_mark("noise", out, scale=scale)
            newp.append(out.reshape(p.shape).astype(p.dtype))
        new_params = jax.tree.unflatten(jax.tree.structure(params), newp)
        if momentum_buf is None:
            return new_params, None
        tail = view.total - view.n_params
        if tail:
            newm_segs.append(jnp.zeros((tail,), jnp.float32))
        return new_params, jnp.concatenate(newm_segs)

    # pure-XLA flat-fused path: one expression over the flat buffers; the
    # per-leaf static slices below are views XLA fuses into the update loop
    if key is not None:
        z = dp_mark("noise", view.noise(key), scale=scale)
        g_flat = (grad_acc + sigma_c * z) * (1.0 / expected_batch)
    else:
        g_flat = grad_acc * (1.0 / expected_batch)
    if momentum_buf is not None:
        new_mom = momentum * momentum_buf + g_flat
        use = new_mom
    else:
        new_mom = None
        use = g_flat
    newp = [(p.astype(jnp.float32) - lr * view.segment(use, i)).astype(p.dtype)
            for i, p in enumerate(leaves)]
    return jax.tree.unflatten(jax.tree.structure(params), newp), new_mom
