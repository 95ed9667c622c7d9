"""Pallas TPU kernel: per-example ghost gradient sq-norms for dense layers.

Computes  n[b] = ‖ X_bᵀ dY_b ‖²_F  without materialising the (din, dout)
per-example gradient in HBM: each program forms one MXU-aligned
(TILE_I, TILE_O) block of X_bᵀ dY_b in a VMEM accumulator (f32), reduces it to
a partial sum of squares, and accumulates into n[b] across the (i, j) grid.
The T axis is streamed in TILE_T slabs inside the program, so VMEM holds only
(TILE_T×TILE_I) + (TILE_T×TILE_O) + (TILE_I×TILE_O) floats.

This is the direct O(T·din·dout) path of Mixed Ghost Clipping; on TPU it is
preferred whenever T² > din·dout — exactly the paper's selection rule, but
tiled for VMEM/MXU instead of cuBLAS.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_I = 128
TILE_O = 128
TILE_T = 128


def _kernel(x_ref, dy_ref, out_ref, *, tt: int):
    # x (1, T, TILE_I), dy (1, T, TILE_O) -> scalar partial, broadcast over
    # example b's whole (1, 8, 128) output block (one f32 vreg: the smallest
    # block the TPU tiling accepts; the caller reads element [b, 0, 0])
    T = x_ref.shape[1]
    nt = T // tt

    def body(t, acc):
        xs = x_ref[0, pl.dslice(t * tt, tt), :]      # (TT, TI)
        ds = dy_ref[0, pl.dslice(t * tt, tt), :]     # (TT, TO)
        return acc + jnp.dot(xs.T, ds, preferred_element_type=jnp.float32)

    m = jax.lax.fori_loop(0, nt, body,
                          jnp.zeros((x_ref.shape[2], dy_ref.shape[2]),
                                    jnp.float32))
    partial = jnp.sum(m * m)

    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("interpret", "tiles"))
def ghost_norm_dense(x, dy, *, interpret: bool,
                     tiles=(TILE_I, TILE_O, TILE_T)):
    """x (B, T, din), dy (B, T, dout) -> (B,) per-example ‖XᵀdY‖²_F."""
    ti, to, tt = tiles
    B, T, di = x.shape
    do = dy.shape[-1]

    def padto(a, ax, m):
        p = (-a.shape[ax]) % m
        if p:
            pads = [(0, 0)] * a.ndim
            pads[ax] = (0, p)
            a = jnp.pad(a, pads)
        return a

    x = padto(padto(x, 1, tt), 2, ti).astype(jnp.float32)
    dy = padto(padto(dy, 1, tt), 2, to).astype(jnp.float32)
    Tp, dip, dop = x.shape[1], x.shape[2], dy.shape[2]

    kern = functools.partial(_kernel, tt=tt)
    out = pl.pallas_call(
        kern,
        grid=(B, dip // ti, dop // to),
        in_specs=[
            pl.BlockSpec((1, Tp, ti), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, Tp, to), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 8, 128), lambda b, i, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 8, 128), jnp.float32),
        interpret=interpret,
    )(x, dy)
    return out[:, 0, 0]
