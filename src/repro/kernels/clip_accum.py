"""Pallas TPU kernel: fused per-example clip + Poisson mask + accumulate.

Paper Table 2 shows "clip and accumulation" is a separate 26.76 ms pass in
Opacus because it re-reads every per-example gradient from HBM after the
norms are known.  On TPU we fuse coefficient computation (mask · min(1, C/‖g‖))
with the weighted reduction so the per-example gradient block is read from
HBM exactly once, streamed through VMEM tiles.

    out[d] = Σ_b  mask[b] · min(1, C / norm[b]) · g[b, d]

Grid: one program per D-tile; the B axis is reduced inside the kernel.

Two entry points:

  * :func:`clip_accum` — the resident form: all B per-example gradient rows
    exist at once (the ``masked_fused`` engine).
  * :func:`clip_accum_inplace` — the streaming form: an m-row tile of
    per-example gradients is clipped and added into an existing flat f32
    accumulator, which is passed as an ALIASED input/output operand
    (``input_output_aliases``), so XLA updates the buffer in place — inside
    a ``lax.scan`` over tiles the accumulator never duplicates across
    iterations.  The caller guarantees the flat length divides the D-tile
    (FlatGradView totals are 256-aligned); no padding copy may happen here,
    it would break the aliasing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_D = 1024


def _opaque_count(n: int):
    # the fold's trip count as an operand XLA cannot constant-fold: a
    # literal count of 1 would re-unroll the loop and reintroduce the FMA
    # contraction _fold_rows exists to avoid
    return jax.lax.optimization_barrier(jnp.full((1,), n, jnp.int32))


def _clip_rows(g_ref, norm_ref, mask_ref, c_ref, w_ref):
    # per-example grads arrive in their storage dtype (f32 or bf16 under
    # pe_bf16) and are upcast per VMEM tile — no f32 HBM copy upstream.
    # The weighted rows land in VMEM scratch BEFORE the fold, so the fold
    # is adds only (no multiply left to contract into an FMA)
    norms = norm_ref[...]                # (B, 1)
    coef = mask_ref[...] * jnp.minimum(1.0, c_ref[0] / jnp.maximum(norms,
                                                                   1e-12))
    w_ref[...] = g_ref[...].astype(jnp.float32) * coef


def _fold_rows(w_ref, init, n):
    # strict left fold over the example axis — the engines' CANONICAL
    # reduction order (matches masked_pe's lax.scan fold bitwise, and
    # composes across microbatch tiles, which jnp.sum's XLA-internal reduce
    # order does not).  Two things are load-bearing for the bits: the
    # sequential loop primitive (an unrolled python loop lets XLA
    # FMA-contract the row multiply into the adds) AND the DATA-DEPENDENT
    # trip count ``n`` (a static bound of 1 is constant-unrolled and
    # contracted the same way — observed on XLA:CPU).  Rows are read from
    # the ref with ``pl.ds``: Mosaic lowers a dynamic ref slice, not a
    # dynamic_slice of a value.
    def body(b, a):
        return a + w_ref[pl.ds(b, 1), :]
    return jax.lax.fori_loop(0, n, body, init)


def _kernel(g_ref, norm_ref, mask_ref, c_ref, n_ref, out_ref, w_ref):
    _clip_rows(g_ref, norm_ref, mask_ref, c_ref, w_ref)
    out_ref[...] = _fold_rows(w_ref, jnp.zeros(out_ref.shape, jnp.float32),
                              n_ref[0])


def _specs(rows: int, tile_d: int):
    # (grads tile, norms, mask) in VMEM; clip norm and trip count as SMEM
    # scalars; the weighted-rows scratch the fold reads back
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return ([pl.BlockSpec((rows, tile_d), lambda i: (0, i)),
             pl.BlockSpec((rows, 1), lambda i: (0, 0)),
             pl.BlockSpec((rows, 1), lambda i: (0, 0)),
             smem, smem],
            [pltpu.VMEM((rows, tile_d), jnp.float32)])


def _scalars(norms, mask, clip_norm, rows: int):
    return (norms.astype(jnp.float32).reshape(rows, 1),
            mask.astype(jnp.float32).reshape(rows, 1),
            jnp.asarray(clip_norm, jnp.float32).reshape(1),
            _opaque_count(rows))


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def clip_accum(grads, norms, mask, clip_norm, *, interpret: bool,
               tile_d=TILE_D):
    """grads (B, D) f32/bf16; norms (B,); mask (B,); clip_norm -> (D,) f32."""
    B, D = grads.shape
    pad = (-D) % tile_d
    if pad:
        grads = jnp.pad(grads, ((0, 0), (0, pad)))
    Dp = D + pad
    in_specs, scratch = _specs(B, tile_d)
    out = pl.pallas_call(
        _kernel,
        grid=(Dp // tile_d,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tile_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(grads, *_scalars(norms, mask, clip_norm, B))
    return out[0, :D]


def _kernel_acc(acc_ref, g_ref, norm_ref, mask_ref, c_ref, n_ref, out_ref,
                w_ref):
    # same clip+reduce as _kernel, with the running accumulator tile added —
    # out aliases acc, so this is an in-place += on the flat buffer.
    # Folding FROM the carry (not carry + tile-sum) is what makes the total
    # identical for every tile size m: the full scan is one long fold
    _clip_rows(g_ref, norm_ref, mask_ref, c_ref, w_ref)
    out_ref[...] = _fold_rows(w_ref, acc_ref[...], n_ref[0])


def pick_tile_d(total: int, tile_d: int = TILE_D) -> int:
    """Largest kernel D-tile in {tile_d, 512, 256} dividing ``total``
    (FlatGradView totals are 256-aligned, so 256 always works there);
    falls back to one whole-buffer program for odd test sizes."""
    for t in (tile_d, 512, 256):
        if total % t == 0:
            return t
    return total


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def clip_accum_inplace(acc, grads, norms, mask, clip_norm, *,
                       interpret: bool, tile_d=None):
    """acc (D,) f32 += Σ_b mask·min(1, C/norm)·grads[b]; acc is aliased.

    ``grads`` is an (m, D) tile in its storage dtype; ``D`` must be a
    multiple of the resolved ``tile_d`` — the caller pads ONCE outside any
    scan (a pad here would copy and defeat ``input_output_aliases``).
    """
    m, D = grads.shape
    if acc.shape != (D,):
        raise ValueError(
            f"acc shape {acc.shape} must match the padded grad row ({D},); "
            f"pad the tile to the accumulator layout before the call")
    if tile_d is None:
        # interpret mode simulates the grid program-by-program with real
        # per-program overhead and no VMEM limit to respect — one
        # whole-buffer program keeps the scan-of-kernels cheap off-TPU
        tile_d = D if interpret else pick_tile_d(D)
    if D % tile_d:
        raise ValueError(
            f"flat length {D} must divide the kernel tile {tile_d} "
            f"(FlatGradView totals are 256-aligned; pass tile_d=... for "
            f"other layouts)")
    in_specs, scratch = _specs(m, tile_d)
    out = pl.pallas_call(
        _kernel_acc,
        grid=(D // tile_d,),
        in_specs=[pl.BlockSpec((1, tile_d), lambda i: (0, i))] + in_specs,
        out_specs=pl.BlockSpec((1, tile_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        scratch_shapes=scratch,
        input_output_aliases={0: 0},
        interpret=interpret,
    )(acc.reshape(1, D), grads, *_scalars(norms, mask, clip_norm, m))
    return out[0]
