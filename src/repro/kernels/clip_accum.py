"""Pallas TPU kernel: fused per-example clip + Poisson mask + accumulate.

Paper Table 2 shows "clip and accumulation" is a separate 26.76 ms pass in
Opacus because it re-reads every per-example gradient from HBM after the
norms are known.  On TPU we fuse coefficient computation (mask · min(1, C/‖g‖))
with the weighted reduction so the per-example gradient block is read from
HBM exactly once, streamed through VMEM tiles.

    out[d] = Σ_b  mask[b] · min(1, C / norm[b]) · g[b, d]

Grid: one program per D-block of ``tile_d`` lanes, ``pl.cdiv(D, tile_d)``
programs with a partial last block (its lanes past D are never stored).
The block is as wide as a VMEM budget allows for the call's row count and
dtype (:func:`pick_tile_d`): a grid step has a fixed cost, so narrow blocks
of a long flat buffer are bound by step overhead, not by HBM.  Inside a
block the rows are clipped and folded one lane chunk at a time, so the
weighted-rows scratch and the running sum are one chunk wide.

Two entry points:

  * :func:`clip_accum` — the resident form: all B per-example gradient rows
    exist at once (the ``masked_fused`` engine).
  * :func:`clip_accum_inplace` — the streaming form: an m-row tile of
    per-example gradients is clipped and added into an existing flat f32
    accumulator, which is passed as an ALIASED input/output operand
    (``input_output_aliases``), so XLA updates the buffer in place — inside
    a ``lax.scan`` over tiles the accumulator never duplicates across
    iterations.  Any flat length works; no padding copy may happen here,
    it would break the aliasing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# lanes clipped and folded per inner step.  The fold's row loop costs a
# fixed time per chunk, so a wide chunk (32 KiB of f32 per row) beats one
# whose carry would fit the vector registers
LANE_CHUNK = 8192
# VMEM the blocks of one call may take; below v5e's default scoped limit
VMEM_BUDGET = 14 * 2**20
DEFAULT_SCOPED_VMEM = 16 * 2**20


def _opaque_count(n: int):
    # the fold's trip count as an operand XLA cannot constant-fold: a
    # literal count of 1 would re-unroll the loop and reintroduce the FMA
    # contraction _fold_rows exists to avoid
    return jax.lax.optimization_barrier(jnp.full((1,), n, jnp.int32))


def _padded_rows(rows: int, itemsize: int) -> int:
    # rows a VMEM buffer holds after sublane tiling: 8 of f32, 16 of bf16
    sub = 8 * 4 // itemsize
    return -(-rows // sub) * sub


def vmem_bytes(rows: int, itemsize: int, tile_d: int, chunk: int,
               acc: bool) -> int:
    """VMEM one call takes: the double-buffered (rows, tile_d) gradient
    block, the double-buffered (1, tile_d) f32 accumulator (``acc``) and
    output rows, sublane-padded to 8, the (rows, 1) norm and mask blocks
    and the (rows, chunk) f32 scratch of weighted rows."""
    grads = 2 * _padded_rows(rows, itemsize) * itemsize * tile_d
    flat_rows = (4 if acc else 2) * 8 * 4 * tile_d
    scalars = 2 * 2 * _padded_rows(rows, 4) * LANES * 4
    return grads + flat_rows + scalars + _padded_rows(rows, 4) * 4 * chunk


def pick_tile_d(total: int, rows: int, itemsize: int, *, acc: bool) -> int:
    """The widest D-block, a multiple of :data:`LANE_CHUNK`, whose call
    fits :data:`VMEM_BUDGET` (:func:`vmem_bytes`) for ``rows`` gradient
    rows of ``itemsize`` bytes; never below one chunk, and the whole
    buffer when that is narrower."""
    fixed = vmem_bytes(rows, itemsize, 0, LANE_CHUNK, acc)
    per_lane = vmem_bytes(rows, itemsize, 1, LANE_CHUNK, acc) - fixed
    tile = max(LANE_CHUNK,
               (VMEM_BUDGET - fixed) // per_lane // LANE_CHUNK * LANE_CHUNK)
    return min(tile, total)


def _clip_rows(g, norm_ref, mask_ref, c_ref, w_ref):
    # per-example grads arrive in their storage dtype (f32 or bf16 under
    # pe_bf16) and are upcast per VMEM chunk — no f32 HBM copy upstream.
    # The weighted rows land in VMEM scratch BEFORE the fold, so the fold
    # is adds only (no multiply left to contract into an FMA)
    norms = norm_ref[...]                # (B, 1)
    coef = mask_ref[...] * jnp.minimum(1.0, c_ref[0] / jnp.maximum(norms,
                                                                   1e-12))
    w_ref[:, :g.shape[1]] = g.astype(jnp.float32) * coef


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
    width = init.shape[1]

    def body(b, a):
        return a + w_ref[pl.ds(b, 1), :width]
    return jax.lax.fori_loop(0, n, body, init)


def _fold_block(g_ref, norm_ref, mask_ref, c_ref, n_ref, out_ref, w_ref,
                acc_ref=None):
    # one D-block, chunk by chunk: clip the chunk's rows into scratch, then
    # fold them onto the chunk's accumulator (or +0) and store.  Each
    # element still sees acc + w0 + w1 + ... + w(n-1), whatever the block
    # and chunk widths
    chunk = w_ref.shape[1]
    n = n_ref[0]

    def chunk_at(start, width):
        cols = pl.ds(start, width)
        _clip_rows(g_ref[:, cols], norm_ref, mask_ref, c_ref, w_ref)
        init = (acc_ref[:, cols] if acc_ref is not None
                else jnp.zeros((1, width), jnp.float32))
        out_ref[:, cols] = _fold_rows(w_ref, init, n)

    def body(j, carry):
        chunk_at(pl.multiple_of(j * chunk, chunk), chunk)
        return carry

    full, tail = divmod(out_ref.shape[1], chunk)
    if full == 1:
        # a static start: a whole-buffer block of an odd length has a
        # chunk that is no multiple of the 128-lane tile
        chunk_at(0, chunk)
    else:
        jax.lax.fori_loop(0, full, body, 0)
    if tail:
        chunk_at(full * chunk, tail)


def _kernel(g_ref, norm_ref, mask_ref, c_ref, n_ref, out_ref, w_ref):
    _fold_block(g_ref, norm_ref, mask_ref, c_ref, n_ref, out_ref, w_ref)


def _kernel_acc(acc_ref, g_ref, norm_ref, mask_ref, c_ref, n_ref, out_ref,
                w_ref):
    # same clip+reduce as _kernel, with the running accumulator block added —
    # out aliases acc, so this is an in-place += on the flat buffer.
    # Folding FROM the carry (not carry + tile-sum) is what makes the total
    # identical for every tile size m: the full scan is one long fold
    _fold_block(g_ref, norm_ref, mask_ref, c_ref, n_ref, out_ref, w_ref,
                acc_ref)


def _call(kernel, grads, norms, mask, clip_norm, *, interpret: bool,
          tile_d, acc=None):
    rows, D = grads.shape
    itemsize = grads.dtype.itemsize
    # interpret mode simulates the grid program-by-program with real
    # per-program overhead and no VMEM limit to respect — one whole-buffer
    # program keeps the scan-of-kernels cheap off-TPU
    if tile_d is None:
        tile_d = D if interpret else pick_tile_d(D, rows, itemsize,
                                                 acc=acc is not None)
    tile_d = min(tile_d, D)
    chunk = min(LANE_CHUNK, tile_d)
    vmem = vmem_bytes(rows, itemsize, tile_d, chunk, acc is not None)
    params = (pltpu.CompilerParams(vmem_limit_bytes=vmem + 2**20)
              if vmem > DEFAULT_SCOPED_VMEM and not interpret else None)
    # (grads block, norms, mask) in VMEM; clip norm and trip count as SMEM
    # scalars; the weighted-rows scratch of one chunk the fold reads back.
    # The operands keep their shapes — (1, D) f32 accumulator, (rows, D)
    # rows in their storage dtype — at the pallas_call
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    flat = pl.BlockSpec((1, tile_d), lambda i: (0, i))
    in_specs = [pl.BlockSpec((rows, tile_d), lambda i: (0, i)),
                pl.BlockSpec((rows, 1), lambda i: (0, 0)),
                pl.BlockSpec((rows, 1), lambda i: (0, 0)),
                smem, smem]
    operands = (grads,
                norms.astype(jnp.float32).reshape(rows, 1),
                mask.astype(jnp.float32).reshape(rows, 1),
                jnp.asarray(clip_norm, jnp.float32).reshape(1),
                _opaque_count(rows))
    if acc is not None:
        in_specs = [flat] + in_specs
        operands = (acc.reshape(1, D),) + operands
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(D, tile_d),),
        in_specs=in_specs,
        out_specs=flat,
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, chunk), jnp.float32)],
        input_output_aliases={0: 0} if acc is not None else {},
        compiler_params=params,
        interpret=interpret,
    )(*operands)
    return out[0]


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def clip_accum(grads, norms, mask, clip_norm, *, interpret: bool,
               tile_d=None):
    """grads (B, D) f32/bf16; norms (B,); mask (B,); clip_norm -> (D,) f32.

    ``tile_d`` (lanes per D-block) defaults to the widest block the VMEM
    budget allows for B rows."""
    return _call(_kernel, grads, norms, mask, clip_norm,
                 interpret=interpret, tile_d=tile_d)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_d"))
def clip_accum_inplace(acc, grads, norms, mask, clip_norm, *,
                       interpret: bool, tile_d=None):
    """acc (D,) f32 += Σ_b mask·min(1, C/norm)·grads[b]; acc is aliased.

    ``grads`` is an (m, D) tile in its storage dtype, already in the
    accumulator's layout — the caller pads ONCE outside any scan (a pad
    here would copy and defeat ``input_output_aliases``).  ``tile_d`` as
    in :func:`clip_accum`, with the budget counting the accumulator's
    block too.
    """
    D = grads.shape[1]
    if acc.shape != (D,):
        raise ValueError(
            f"acc shape {acc.shape} must match the padded grad row ({D},); "
            f"pad the tile to the accumulator layout before the call")
    return _call(_kernel_acc, grads, norms, mask, clip_norm,
                 interpret=interpret, tile_d=tile_d, acc=acc)
