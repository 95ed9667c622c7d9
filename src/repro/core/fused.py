"""The fused clipping engines: Pallas clip+accumulate, resident and streaming.

Paper Table 2 shows "clip and accumulation" as a separate 26.76 ms pass in
Opacus because the per-example gradients are re-read from HBM once the norms
are known.  Two engines attack that cost at different depths:

``masked_fused`` computes per-example gradients exactly like ``masked_pe``
(the shared :func:`~repro.core.clipping.per_example_grads_and_sq` plumbing —
same norms, same coefficients) but hands the masked weighted reduction

    out[d] = sum_b  mask[b] * min(1, C / ||g_b||) * g[b, d]

to :func:`repro.kernels.tree_clip_accum`, whose Pallas kernel streams the
flattened per-example gradient matrix through VMEM tiles exactly once (in
its native dtype — bf16 per-example grads stay bf16 until the in-kernel
upcast).  Its peak memory is still O(B·params): the whole vmapped gradient
tree is resident when the kernel runs.

``masked_fused_stream`` never materialises that tree.  The backward runs as
a ``lax.scan`` over microbatch tiles of m ≪ B examples; each iteration
vmaps per-example grads for its tile only, clips them, and adds the tile's
clipped sum STRAIGHT into the flat f32 accumulator through
:func:`repro.kernels.flat_clip_accum`, whose Pallas kernel takes the
accumulator as an aliased input/output operand (``input_output_aliases``) —
XLA updates the buffer in place across scan iterations.  Peak live memory
drops to O(m·params + params); ``m`` comes from ``DPConfig.stream_tile`` or
the :func:`repro.launch.costmodel.stream_tile_size` budget rule.

Clip coefficients are purely per-example (no cross-example dependency), so
streaming needs no second backward in the default configuration: each
tile's norms are computed from that tile's own vmapped grads — numerically
THE masked_pe expressions, which is what makes the engine bitwise-identical
to ``masked_pe`` (same flat noise stream ⇒ identical updates).  The
two-pass form the ghost-clipping literature uses — full-batch norms via the
ghost trick first, then the clip-and-accumulate backward — is available by
switching the norm source (:func:`set_stream_norm_source`); it trades a
second backward for never touching per-example grads in the norm pass, and
matches masked_pe only to ghost-norm tolerance (~5e-3), like
``masked_ghost`` itself.

On CPU the kernels run in interpret mode, so both engines are testable
(and parity with ``masked_pe`` is asserted) everywhere.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import flat_clip_accum, interpret_mode, tree_clip_accum
from ..utils.params import FlatGradView
from .clipping import (Aux, ShardingConstraints, clip_coef, ghost_norms,
                       per_example_grads_and_sq, register_engine)


@register_engine("masked_fused", materializes_pe=True)
def fused_clipped_grads(loss_fn: Callable, params, batch, mask,
                        clip_norm: float, *,
                        constraints: Optional[ShardingConstraints] = None
                        ) -> Tuple[dict, Aux]:
    grads, sq = per_example_grads_and_sq(loss_fn, params, batch, constraints)
    # kernel recomputes mask * min(1, C/norm) internally; coef here is aux
    coef, norms = clip_coef(sq, mask, clip_norm)
    summed = tree_clip_accum(grads, norms, mask, clip_norm,
                             interpret=interpret_mode())
    return summed, {"per_example_norms": norms, "clip_coef": coef}


# ---------------------------------------------------------------------------
# streaming fused clipping
# ---------------------------------------------------------------------------

# where the streaming engine's per-example norms come from:
#   "pe"    — each tile's own vmapped grads (single backward total; bitwise
#             masked_pe numerics) — the default;
#   "ghost" — a full-batch ghost-norm pass first (no per-example grads in
#             the norm pass), then the tiled clip-and-accumulate backward
#             with the precomputed coefficients — the literal two-pass form.
_NORM_SOURCES = ("pe", "ghost")
_stream_norm_source = "pe"


def set_stream_norm_source(source: str) -> str:
    """Switch the streaming engine's norm pass; returns the previous value
    (restore it in a finally:, like layers._FORCE_PATH)."""
    global _stream_norm_source
    if source not in _NORM_SOURCES:
        raise ValueError(f"norm source {source!r}; expected {_NORM_SOURCES}")
    prev = _stream_norm_source
    _stream_norm_source = source
    return prev


def _default_stream_tile(batch_size: int, n_params: int) -> int:
    # lazy import: launch.costmodel is a leaf module, but keep core free of
    # launch imports at module load (executor <-> session already tiptoe)
    from ..launch.costmodel import stream_tile_size
    return stream_tile_size(batch_size, n_params)


@register_engine("masked_fused_stream", streaming=True)
def streaming_clipped_grads(loss_fn: Callable, params, batch, mask,
                            clip_norm: float, *,
                            constraints: Optional[ShardingConstraints] = None,
                            acc=None, view: Optional[FlatGradView] = None,
                            tile: Optional[int] = None) -> Tuple[jnp.ndarray,
                                                                 Aux]:
    """Clip-and-accumulate per-example grads without the O(B·params) tree.

    Called by ``build_accumulate_fn`` with ``acc``/``view``/``tile`` (the
    streaming contract — returns the new flat accumulator).  Standalone
    calls (tests, notebooks) may omit ``acc``: the engine starts from zeros
    and returns the summed gradient TREE like every other engine.
    """
    standalone = acc is None
    if view is None:
        view = FlatGradView.for_tree(params)
    if acc is None:
        acc = view.zeros()
    B = int(mask.shape[0])
    m = int(tile) if tile else _default_stream_tile(B, view.n_params)
    m = max(1, min(m, B))

    # pad the batch to a tile multiple by repeating example 0 with mask 0:
    # coef = 0 exactly, so padded rows contribute exact zeros to the sums
    pad = (-B) % m
    if pad:
        batch = jax.tree.map(
            lambda x: jnp.concatenate([x] + [x[:1]] * pad, axis=0), batch)
        mask = jnp.concatenate([mask, jnp.zeros((pad,), mask.dtype)])
    n_tiles = (B + pad) // m

    ghost = _stream_norm_source == "ghost"
    if ghost:
        # pass 1: full-batch per-example norms with NO per-example grads
        sq_all, _ = ghost_norms(loss_fn, params, batch)
        norms_all = jnp.sqrt(jnp.maximum(sq_all, 1e-24))
        # the recognised clip site for the precomputed coefficients
        coef_all, _ = clip_coef(sq_all, mask, clip_norm)

    def resh(x):
        return x.reshape((n_tiles, m) + x.shape[1:])

    xs = (jax.tree.map(resh, batch), resh(mask))
    if ghost:
        xs = xs + (resh(norms_all), resh(coef_all))

    tile_hook = constraints.tile_batch if constraints is not None else None
    kernel_map = constraints.kernel_map if constraints is not None else None
    interpret = interpret_mode()
    pad_d = view.total - view.n_params
    # XLA lowers a width-1 batched backward through a different dot path
    # than the same row inside a wider vmap (the batch dim degenerates),
    # which shifts gradient bits — so an m=1 tile is vmapped at width 2
    # with a zero-masked duplicate row, whose fold contribution is an
    # exact ±0 add.  One duplicated backward per tile is the price of
    # keeping m=1 on the canonical bit pattern.
    m_eff = max(m, 2)

    def body(carry, xs):
        if ghost:
            b, mk, norms, coef = xs
        else:
            b, mk = xs
        if m_eff != m:
            b = jax.tree.map(
                lambda x: jnp.concatenate([x, x[:1]], axis=0), b)
            mk = jnp.concatenate([mk, jnp.zeros((1,), mk.dtype)])
            if ghost:
                norms = jnp.concatenate([norms, jnp.ones((1,), norms.dtype)])
        if tile_hook is not None:
            b, mk = tile_hook(b), tile_hook(mk)
        # pass 2 (or the only pass): vmapped grads for THIS tile only —
        # peak live per-example state is m rows, not B
        grads, sq = per_example_grads_and_sq(loss_fn, params, b, constraints)
        if not ghost:
            coef, norms = clip_coef(sq, mk, clip_norm)
        leaves = jax.tree.leaves(grads)
        tile_flat = (jnp.concatenate([l.reshape(m_eff, -1) for l in leaves],
                                     axis=1)
                     if len(leaves) > 1 else leaves[0].reshape(m_eff, -1))
        if pad_d:
            # pad the m-row tile (O(m·params)) to the accumulator layout;
            # the accumulator itself is NEVER padded/copied here — that
            # would break the kernel's input/output aliasing
            tile_flat = jnp.pad(tile_flat, ((0, 0), (0, pad_d)))
        if kernel_map is None:
            carry = flat_clip_accum(carry, tile_flat, norms, mk, clip_norm,
                                    interpret=interpret)
        else:
            # on a mesh each device folds its rows of the tile from zero
            # and the partial sums are all-reduced into the carry
            carry = carry + kernel_map(
                lambda g, n, k: flat_clip_accum(
                    jnp.zeros((g.shape[1],), jnp.float32), g, n, k,
                    clip_norm, interpret=interpret), rows=3)(
                        tile_flat, norms, mk)
        # aux reports the tile's m real examples (drop the vmap-width pad)
        return carry, (norms[:m], coef[:m])

    acc, (norms, coefs) = jax.lax.scan(body, acc, xs)
    aux = {"per_example_norms": norms.reshape(-1)[:B],
           "clip_coef": coefs.reshape(-1)[:B]}
    if standalone:
        return view.unflatten(acc), aux
    return acc, aux
