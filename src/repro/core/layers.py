"""DP layer primitives.

Every parameterised op in the model zoo goes through one of five primitives:

    dense            y = x @ W (+ b)
    embed            y = E[ids]
    scale            y = x * g          (g broadcast over batch/time)
    bias             y = x + b          (b broadcast over batch/time)
    conv1d_depthwise y = causal depthwise conv (Mamba2's conv frontend)

Each primitive supports the Tape protocol (plain / collect / record) and comes
with two analytic companions used by the clipping engines:

    per_example_sq_norm(spec, record, dY) -> (B,) per-example squared grad norms
    bk_grads(spec, record, dY, coef)      -> {param_path: clipped summed grad}

Together these implement Ghost Clipping (Li et al., 2022) and Book-Keeping
(Bu et al., 2023) in JAX, generalised to scan-stacked layers and exact
parameter re-use (Zamba2's shared blocks).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..kernels import ghost_norm_dense, interpret_mode
from .tape import LayerSpec, Tape

# Flip to force one ghost-vs-direct path in tests.
_FORCE_PATH: Optional[str] = None

# Backend for the dense direct-path norm ‖X_bᵀdY_b‖²_F:
#   "auto"   — the Pallas kernel (interpret mode off-TPU), the default
#   "xla"    — the pure-XLA einsum, kept as the everywhere-fallback
_NORM_BACKEND = "auto"


def set_norm_backend(mode: str) -> None:
    """Select the dense direct-path norm backend ("auto" | "xla")."""
    global _NORM_BACKEND
    if mode not in ("auto", "xla"):
        raise ValueError(f"norm backend {mode!r}; expected 'auto' or 'xla'")
    _NORM_BACKEND = mode


def _norm_tiles(T: int, di: int, do: int):
    """Full 128 (sublane×lane-legal) tiles on TPU — Mosaic cannot lower a
    trailing tile below 128 for f32, the kernel pads instead; shape-fitted
    8-aligned tiles in interpret mode so the padded smoke shapes stay tiny."""
    if not interpret_mode():
        return (128, 128, 128)
    r8 = lambda n: -(-n // 8) * 8
    return (min(128, r8(di)), min(128, r8(do)), min(128, r8(T)))


# ---------------------------------------------------------------------------
# forward primitives
# ---------------------------------------------------------------------------

def dense(tape: Tape, name: str, x, w, b=None, *, param_path: str,
          precision=None):
    """y[..., o] = x[..., i] @ w[i, o] + b[o]."""
    y = jnp.einsum("...i,io->...o", x, w, precision=precision,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    spec = LayerSpec("dense", param_path=param_path,
                     meta=(("has_bias", b is not None),))
    return tape.inject(name, y, spec, {"x": x})


def dense_stacked(tape: Tape, name: str, x, w, *, param_path: str,
                  precision=None):
    """Per-expert dense: x (E, ..., i), w (E, i, o) -> (E, ..., o).

    The leading E axis is registered as a 'layers' stack axis, so expert
    weights get exact per-example ghost norms / BK grads like scan-stacked
    layers do (expert-parallel MoE without per-example gradients).
    """
    y = jnp.einsum("e...i,eio->e...o", x, w, precision=precision,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    spec = LayerSpec("dense", param_path=param_path,
                     meta=(("has_bias", False),), stack=("layers",))
    return tape.inject(name, y, spec, {"x": x})


def dense_stacked_pair(tape: Tape, name: str, x, w1, w3, *,
                       param_path1: str, param_path2: str, precision=None):
    """Two per-expert denses sharing one input (SwiGLU's gate/up): the input
    is recorded ONCE — halves MoE record memory vs two dense_stacked calls.
    The second spec carries a ``record_of`` pointer the engines resolve."""
    y1 = jnp.einsum("e...i,eio->e...o", x, w1, precision=precision,
                    preferred_element_type=jnp.float32).astype(x.dtype)
    y2 = jnp.einsum("e...i,eio->e...o", x, w3, precision=precision,
                    preferred_element_type=jnp.float32).astype(x.dtype)
    s1 = LayerSpec("dense", param_path=param_path1,
                   meta=(("has_bias", False),), stack=("layers",))
    s2 = LayerSpec("dense", param_path=param_path2,
                   meta=(("has_bias", False), ("record_of", f"{name}.a")),
                   stack=("layers",))
    y1 = tape.inject(f"{name}.a", y1, s1, {"x": x})
    y2 = tape.inject(f"{name}.b", y2, s2, {})
    return y1, y2


def resolve_record(records, name: str, spec: LayerSpec, scope_name: Optional[str] = None):
    """Return the record for ``name``, following a ``record_of`` alias within
    the same scope (the alias is scope-relative; prefix with this record's
    scope path)."""
    ref = spec.get("record_of")
    if not ref:
        return records[name]
    # name may be scoped ('blocks/moe.w13.b'); the alias shares the prefix
    prefix = name.rsplit("/", 1)[0] + "/" if "/" in name else ""
    local = name.rsplit("/", 1)[-1]
    # alias refers to the sibling primitive: swap the local part
    return records[prefix + ref]


def embed(tape: Tape, name: str, ids, table, *, param_path: str):
    """y = table[ids]; ids int (..., T)."""
    y = jnp.take(table, ids, axis=0)
    spec = LayerSpec("embed", param_path=param_path,
                     meta=(("vocab", table.shape[0]),))
    return tape.inject(name, y, spec, {"ids": ids})


def scale(tape: Tape, name: str, x, g, *, param_path: str):
    """y = x * g with g matching x's trailing dims (e.g. an RMSNorm gain)."""
    y = x * g.astype(x.dtype)
    spec = LayerSpec("scale", param_path=param_path, meta=(("gdim", g.ndim),))
    return tape.inject(name, y, spec, {"x": x})


def bias(tape: Tape, name: str, x, b, *, param_path: str):
    """y = x + b with b matching x's trailing dims."""
    y = x + b.astype(x.dtype)
    spec = LayerSpec("bias", param_path=param_path, meta=(("bdim", b.ndim),))
    return tape.inject(name, y, spec, {})


def conv1d_depthwise(tape: Tape, name: str, x, w, *, param_path: str):
    """Causal depthwise conv: x (B, T, C), w (K, C).

    y[b, t, c] = sum_k w[k, c] * xpad[b, t + k, c],  xpad left-padded by K-1.
    """
    k = w.shape[0]
    xpad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xpad[:, i:i + x.shape[1], :] * w[i].astype(x.dtype) for i in range(k))
    spec = LayerSpec("conv1d", param_path=param_path, meta=(("width", k),))
    return tape.inject(name, y, spec, {"x": x})


# ---------------------------------------------------------------------------
# shape normalisation for the analytic companions
# ---------------------------------------------------------------------------

def _fold(spec: LayerSpec, rec: Dict, dY):
    """Normalise (record, dY) to canonical stacked shapes.

    Layout on entry is (stack..., B, inner...).  'uses' stack axes (same
    parameter re-used each step) are transposed to sit *after* the batch axis,
    where the norm/grad companions treat them as extra token axes — which makes
    cross-use inner products exact.  Remaining leading axes are 'layers' axes
    over which norms add / grads stack.  Returns (rec, dY, n_layer_axes).
    """
    stack = spec.stack
    n = len(stack)
    layer_ax = [i for i, s in enumerate(stack) if s == "layers"]
    use_ax = [i for i, s in enumerate(stack) if s == "uses"]
    if not use_ax:
        return rec, dY, len(layer_ax)

    def fix(a):
        inner = list(range(n + 1, a.ndim))
        return jnp.transpose(a, layer_ax + [n] + use_ax + inner)

    rec = {k: fix(v) for k, v in rec.items()}
    dY = fix(dY)
    return rec, dY, len(layer_ax)


def _as_btd(a, batch_axis0=True):
    """Collapse (B, T..., d) -> (B, T, d); (B, d) -> (B, 1, d)."""
    if a.ndim == 2:
        return a[:, None, :]
    b = a.shape[0]
    d = a.shape[-1]
    return a.reshape(b, -1, d)


def _map_layers(fn, args, n_layer_axes):
    """Apply fn across leading layer axes sequentially (low memory liveness),
    summing the (B,) results over all layer axes."""
    if n_layer_axes == 0:
        return fn(*args)
    args = tuple(a.reshape((-1,) + a.shape[n_layer_axes:]) for a in args)
    out = jax.lax.map(lambda xs: fn(*xs), args)  # (L, B)
    return out.sum(axis=0)


# ---------------------------------------------------------------------------
# per-example squared gradient norms (ghost clipping)
# ---------------------------------------------------------------------------

def _sq_norm_dense_one(x, dy, has_bias):
    """x (B,T,i), dy (B,T,o) -> (B,) squared norm of per-example W (+ b) grads.

    Chooses the ghost path (O(T^2 d)) vs the direct path (O(T i o)) per the
    Mixed-Ghost rule (Bu et al., 2022) — the same selection
    ``launch.costmodel._ghost_norm_flops`` prices.  The direct path runs the
    :func:`repro.kernels.ghost_norm_dense` Pallas kernel (the per-example
    (din, dout) gradient block never leaves VMEM); ``set_norm_backend("xla")``
    falls back to the pure-XLA einsum everywhere.
    """
    x = _as_btd(x)
    dy = _as_btd(dy)
    B, T, di = x.shape
    do = dy.shape[-1]
    use_ghost = (T * T <= di * do) if _FORCE_PATH is None else (_FORCE_PATH == "ghost")
    xf = x.astype(jnp.float32)
    df = dy.astype(jnp.float32)
    if use_ghost and T > 1:
        gx = jnp.einsum("bti,bsi->bts", xf, xf)
        gd = jnp.einsum("bto,bso->bts", df, df)
        nw = jnp.sum(gx * gd, axis=(1, 2))
    elif _NORM_BACKEND != "xla":
        nw = ghost_norm_dense(xf, df,
                              interpret=interpret_mode(),
                              tiles=_norm_tiles(T, di, do))
    else:
        m = jnp.einsum("bti,bto->bio", xf, df)
        nw = jnp.sum(m * m, axis=(1, 2))
    if has_bias:
        gb = df.sum(axis=1)
        nw = nw + jnp.sum(gb * gb, axis=-1)
    return nw


def _sq_norm_embed_one(ids, dy, _):
    """ids (B,T...), dy (B,T...,d): ghost trick on the one-hot design matrix."""
    ids = ids.reshape(ids.shape[0], -1)
    dy = _as_btd(dy)
    df = dy.astype(jnp.float32)
    same = (ids[:, :, None] == ids[:, None, :]).astype(jnp.float32)
    gd = jnp.einsum("btd,bsd->bts", df, df)
    return jnp.sum(same * gd, axis=(1, 2))


def _sq_norm_scale_one(x, dy, gdim):
    """grad_g[b] = sum over non-param axes of x*dy, reduced to g's shape."""
    prod = (x.astype(jnp.float32) * dy.astype(jnp.float32))
    # sum over token axes, keep trailing gdim dims
    red = tuple(range(1, prod.ndim - gdim))
    g = prod.sum(axis=red) if red else prod
    return jnp.sum(g.reshape(g.shape[0], -1) ** 2, axis=-1)


def _sq_norm_bias_one(dy, bdim):
    df = dy.astype(jnp.float32)
    red = tuple(range(1, df.ndim - bdim))
    g = df.sum(axis=red) if red else df
    return jnp.sum(g.reshape(g.shape[0], -1) ** 2, axis=-1)


def _pe_grad_conv1d(x, dy, k):
    """Per-example conv grads (B,K,C) — K is tiny so this is cheap."""
    xpad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    T = x.shape[1]
    df = dy.astype(jnp.float32)
    return jnp.stack([jnp.einsum("btc,btc->bc", xpad[:, i:i + T], df)
                      for i in range(k)], axis=1)


def per_example_sq_norm(spec: LayerSpec, rec: Dict, dY) -> jnp.ndarray:
    rec, dY, nl = _fold(spec, rec, dY)
    if spec.kind == "dense":
        hb = spec.get("has_bias", False)
        return _map_layers(lambda x, d: _sq_norm_dense_one(x, d, hb),
                           (rec["x"], dY), nl)
    if spec.kind == "embed":
        return _map_layers(lambda i, d: _sq_norm_embed_one(i, d, None),
                           (rec["ids"], dY), nl)
    if spec.kind == "scale":
        gd = spec.get("gdim", 1)
        return _map_layers(lambda x, d: _sq_norm_scale_one(x, d, gd),
                           (rec["x"], dY), nl)
    if spec.kind == "bias":
        bd = spec.get("bdim", 1)
        return _map_layers(lambda d: _sq_norm_bias_one(d, bd), (dY,), nl)
    if spec.kind == "conv1d":
        k = spec.get("width")

        def f(x, d):
            g = _pe_grad_conv1d(x, d, k)
            return jnp.sum(g.reshape(g.shape[0], -1) ** 2, axis=-1)
        return _map_layers(f, (rec["x"], dY), nl)
    raise ValueError(spec.kind)


# ---------------------------------------------------------------------------
# book-keeping: clipped summed grads straight from the tape
# ---------------------------------------------------------------------------

def _coef_mul(a, coef, n_layer_axes):
    """Multiply (layers..., B, ...) by per-example coef (B,)."""
    shape = (1,) * n_layer_axes + (coef.shape[0],) + (1,) * (a.ndim - n_layer_axes - 1)
    return a * coef.reshape(shape).astype(a.dtype)


def bk_grads(spec: LayerSpec, rec: Dict, dY, coef) -> Dict[str, jnp.ndarray]:
    """Σ_b coef_b * per-example-grad_b, computed without materialising
    per-example parameter gradients. Keys are '<param_path>' (+ '.b')."""
    rec, dY, nl = _fold(spec, rec, dY)
    dYc = _coef_mul(dY.astype(jnp.float32), coef, nl)
    out = {}
    L = "lmn"[:nl]
    if spec.kind == "dense":
        x = rec["x"].astype(jnp.float32)
        xb = x.reshape(x.shape[:nl + 1] + (-1, x.shape[-1]))
        db = dYc.reshape(dYc.shape[:nl + 1] + (-1, dYc.shape[-1]))
        out[spec.param_path + ".w"] = jnp.einsum(
            f"{L}bti,{L}bto->{L}io", xb, db)
        if spec.get("has_bias", False):
            out[spec.param_path + ".b"] = db.sum(axis=(nl, nl + 1))
        return out
    if spec.kind == "embed":
        V = spec.get("vocab")
        ids = rec["ids"]
        ids = ids.reshape(ids.shape[:nl] + (-1,))
        db = dYc.reshape(dYc.shape[:nl] + (-1, dYc.shape[-1]))

        def scat(args):
            i, d = args
            return jnp.zeros((V, d.shape[-1]), jnp.float32).at[i].add(d)
        if nl == 0:
            g = scat((ids, db))
        else:
            ids_f = ids.reshape((-1,) + ids.shape[nl:])
            db_f = db.reshape((-1,) + db.shape[nl:])
            g = jax.lax.map(scat, (ids_f, db_f)).reshape(
                dYc.shape[:nl] + (V, db.shape[-1]))
        out[spec.param_path] = g
        return out
    if spec.kind == "scale":
        gd = spec.get("gdim", 1)
        prod = rec["x"].astype(jnp.float32) * dYc
        red = tuple(range(nl, prod.ndim - gd))
        out[spec.param_path] = prod.sum(axis=red)
        return out
    if spec.kind == "bias":
        bd = spec.get("bdim", 1)
        red = tuple(range(nl, dYc.ndim - bd))
        out[spec.param_path] = dYc.sum(axis=red)
        return out
    if spec.kind == "conv1d":
        k = spec.get("width")

        def g1(args):
            x, d = args
            return _pe_grad_conv1d(x, d, k).sum(axis=0)
        if nl == 0:
            g = g1((rec["x"], dYc))
        else:
            xf = rec["x"].reshape((-1,) + rec["x"].shape[nl:])
            df = dYc.reshape((-1,) + dYc.shape[nl:])
            g = jax.lax.map(g1, (xf, df)).reshape(
                dYc.shape[:nl] + (k, dYc.shape[-1]))
        out[spec.param_path] = g
        return out
    raise ValueError(spec.kind)
