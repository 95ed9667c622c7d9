"""Clipped per-example gradient computation — the clipping engines of the
paper, behind a pluggable registry.

Every engine maps
    (loss_fn, params, batch, mask, clip_norm, *, constraints)  ->
    (sum of clipped masked per-example grads, aux metrics)
where ``loss_fn(params, batch, tape) -> (B,) per-example losses`` and ``mask``
is the Poisson 0/1 mask of Algorithm 2 (``masked_*`` engines) or all-ones
(``pe`` on an exactly-sampled variable-size batch).

Engines are registered with the :func:`register_engine` decorator and
resolved by name via :func:`resolve_engine` (or the ``ENGINES`` mapping,
kept for backwards compatibility — both give a helpful error listing the
registered names on an unknown engine).

Built-in engines:
  * pe / masked_pe — vmap(grad): materialises per-example grads
                     (Opacus-style); the oracle for everything else.
  * masked_ghost   — two passes: eps-backward for per-example norms (ghost
                     trick), then a reweighted standard backward.  No
                     per-example parameter gradients ever exist.
  * masked_bk      — one pass: the eps-backward's (X, dY) tape is reused to
                     form the clipped summed grads analytically (Bu et al.).

Sharding is passed explicitly via :class:`ShardingConstraints` — resolved by
the executor layer (:mod:`repro.launch.executor`) from the session's
LaunchConfig, or handed in directly by low-level callers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..analysis.marks import mark as dp_mark
from ..obs.phases import phase
from ..utils.params import grads_into_tree, missing_paths
from . import layers
from .tape import Tape

Aux = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# explicit sharding constraints (replaces the mutable module globals)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardingConstraints:
    """Sharding hooks threaded explicitly through the DP step builders.

    grad     — applied to the summed (already clipped) gradient pytree;
               pins it to the parameter (FSDP) layout so GSPMD
               reduce-scatters instead of all-reduce + all-gather.
    grad_flat — applied to the FLAT f32 gradient accumulator
               (``TrainState.grad_acc``); pins its single axis to the data
               axes (offset-range FSDP) so the accumulator never
               materialises replicated under 2d/dp_sp layouts.
    pe_grad  — applied to the vmapped per-example gradient pytree; without
               it GSPMD falls into "involuntary full rematerialization"
               (replicating B x params buffers) on the per-example
               transposes.  Only the pe engines consume it.
    pe_dtype — storage dtype for per-example grads (e.g. jnp.bfloat16
               halves their HBM footprint).
    tile_batch — applied to each microbatch tile (batch leaves + mask) a
               streaming engine scans over; pins the tile's example axis to
               the same data axes the full batch arrived on, so the scanned
               backward stays data-parallel instead of degrading to the
               GSPMD default.  Only streaming engines consume it.
    kernel_map — runs a Pallas kernel call on every device of the mesh:
               ``kernel_map(fn, rows=k)(*args)``, where the first ``k``
               arguments are split over the data axes along their leading
               (example) axis and everything else is replicated, returns
               the sum over devices of ``fn`` on each device's share — so
               ``fn`` must add up over rows (a clipped sum does; with
               ``rows=0`` every device computes the whole result).  GSPMD
               cannot partition a Mosaic kernel, so on a TPU mesh a kernel
               compiles only inside it.  The streaming engine and the fused
               update consume it.
    """
    grad: Optional[Callable] = None
    grad_flat: Optional[Callable] = None
    pe_grad: Optional[Callable] = None
    pe_dtype: Any = None
    tile_batch: Optional[Callable] = None
    kernel_map: Optional[Callable] = None


def _pe_hooks(constraints: Optional[ShardingConstraints]):
    """(pe_grad, pe_dtype) from the constraints, if any."""
    if constraints is not None:
        return constraints.pe_grad, constraints.pe_dtype
    return None, None


# ---------------------------------------------------------------------------
# engine registry
# ---------------------------------------------------------------------------

class EngineRegistry(dict):
    """Name -> engine mapping that fails with the available names listed."""

    def __getitem__(self, name):
        try:
            return super().__getitem__(name)
        except KeyError:
            raise KeyError(
                f"Unknown clipping engine {name!r}. Registered engines: "
                f"{available_engines()} (plus 'nonprivate' for the "
                f"unclipped baseline). Register custom engines with "
                f"@repro.core.clipping.register_engine(name).") from None


ENGINES: "EngineRegistry" = EngineRegistry()


def register_engine(name: str, *aliases: str, materializes_pe: bool = False,
                    record_based: bool = False, streaming: bool = False):
    """Decorator: register a clipping engine under ``name`` (+ aliases).

    An engine is a callable
        fn(loss_fn, params, batch, mask, clip_norm, *, constraints=None)
        -> (summed clipped grads pytree, {"per_example_norms", "clip_coef"})

    Traits (consumed by the executor layer when resolving shardings, and by
    the step builders when dispatching):
      materializes_pe — the engine vmaps real (B x params) per-example
                        gradient buffers, so it needs the pe_grad layout pin
                        under sharded 2d layouts.
      record_based    — the engine's backward keeps per-layer (X, dY)
                        records (ghost/BK style), which sequence-parallel
                        activation sharding keeps T-sharded.
      streaming       — the engine accumulates straight into the flat f32
                        accumulator tile-by-tile instead of returning a
                        summed gradient tree; ``build_accumulate_fn`` calls
                        it with the extra keywords
                        ``acc=<flat buffer>, view=<FlatGradView>,
                        tile=<m or None>`` and receives
                        ``(new flat accumulator, aux)`` back.
    """
    def deco(fn):
        fn.materializes_pe = materializes_pe
        fn.record_based = record_based
        fn.streaming = streaming
        for key in (name,) + aliases:
            if key in ENGINES and dict.__getitem__(ENGINES, key) is not fn:
                raise ValueError(f"clipping engine {key!r} already registered")
            ENGINES[key] = fn
        return fn
    return deco


def resolve_engine(name: str) -> Callable:
    """Look an engine up by name; raises KeyError listing the registry."""
    return ENGINES[name]


def available_engines() -> Tuple[str, ...]:
    return tuple(sorted(ENGINES))


def clip_coef(sq_norms, mask, clip_norm):
    """Opacus clip factor min(1, C/||g||), times the Poisson mask.

    The coefficient is ``dp_mark``-ed as THE recognized clip site: every
    engine that clips by multiplying (pe, ghost's reweighted backward, BK's
    tape recombination) inherits the ``clipped`` taint from this one value,
    so the static verifier (:mod:`repro.analysis`) accepts an aggregation
    only if this coefficient participates in it."""
    with phase("grad/accumulate"):
        norms = jnp.sqrt(jnp.maximum(sq_norms, 1e-24))
        coef = dp_mark("clip", mask * jnp.minimum(1.0, clip_norm / norms))
    return coef, norms


# ---------------------------------------------------------------------------
# per-example (naive / Opacus-style) — oracle for everything else
# ---------------------------------------------------------------------------

def per_example_grads_and_sq(loss_fn: Callable, params, batch,
                             constraints: Optional[ShardingConstraints] = None):
    """vmapped per-example grads (pe_dtype cast + pe_grad pin applied) and
    their per-example squared norms — shared by every pe-style engine so
    dtype/constraint semantics cannot diverge between them."""
    pe_constraint, pe_dtype = _pe_hooks(constraints)

    def one_loss(p, ex):
        ex1 = jax.tree.map(lambda x: x[None], ex)
        return loss_fn(p, ex1, Tape())[0]

    with phase("grad/per_example"):
        grads = jax.vmap(jax.grad(one_loss), in_axes=(None, 0))(params, batch)
        if pe_dtype is not None:
            grads = jax.tree.map(lambda g: g.astype(pe_dtype), grads)
        if pe_constraint is not None:
            grads = pe_constraint(grads)
        sq = sum(jnp.sum(g.reshape(g.shape[0], -1).astype(jnp.float32) ** 2, -1)
                 for g in jax.tree.leaves(grads))
    return grads, sq


@register_engine("pe", "masked_pe", materializes_pe=True)
def per_example_clipped_grads(loss_fn: Callable, params, batch, mask,
                              clip_norm: float, *,
                              constraints: Optional[ShardingConstraints] = None
                              ) -> Tuple[dict, Aux]:
    grads, sq = per_example_grads_and_sq(loss_fn, params, batch, constraints)
    coef, norms = clip_coef(sq, mask, clip_norm)

    def wsum(g):
        c = coef.reshape((-1,) + (1,) * (g.ndim - 1)).astype(jnp.float32)
        w = g.astype(jnp.float32) * c
        # strict left fold over the example axis, from a +0 init — the
        # CANONICAL reduction order.  jnp.sum's reduce order is an XLA
        # implementation detail and not tile-composable; the fold is, so the
        # fused/streaming kernels can reproduce this oracle bitwise for any
        # microbatch tiling (weights are materialised first: a bare
        # multiply-add could FMA-contract differently across lowerings).
        return jax.lax.scan(lambda a, r: (a + r, None),
                            jnp.zeros(w.shape[1:], jnp.float32), w)[0]

    with phase("grad/accumulate"):
        summed = jax.tree.map(wsum, grads)
    return summed, {"per_example_norms": norms, "clip_coef": coef}


def per_example_grad_norms(loss_fn, params, batch) -> jnp.ndarray:
    """Oracle per-example grad norms (B,), used by tests."""
    def one_loss(p, ex):
        ex1 = jax.tree.map(lambda x: x[None], ex)
        return loss_fn(p, ex1, Tape())[0]
    with phase("grad/per_example"):
        grads = jax.vmap(jax.grad(one_loss), in_axes=(None, 0))(params, batch)
        sq = sum(jnp.sum(g.reshape(g.shape[0], -1).astype(jnp.float32) ** 2, -1)
                 for g in jax.tree.leaves(grads))
    return jnp.sqrt(sq)


# ---------------------------------------------------------------------------
# the eps-backward shared by ghost and book-keeping
# ---------------------------------------------------------------------------

def _eps_backward(loss_fn, params, batch):
    """One backward pass w.r.t. the injected eps at every primitive output.

    Returns (dEps, records, specs, losses): per-example output-grads, the
    recorded inputs, the static layer specs, and per-example losses.
    """
    shapes_tape = Tape(Tape.COLLECT)

    def run_collect(p, b):
        nonlocal shapes_tape
        t = Tape(Tape.COLLECT)
        loss_fn(p, b, t)
        shapes_tape = t
        return 0

    jax.eval_shape(run_collect, params, batch)
    eps0 = {n: jnp.zeros(s.shape, s.dtype) for n, s in shapes_tape.eps.items()}

    specs_out: dict = {}

    def f(eps):
        t = Tape(Tape.RECORD, eps)
        losses = loss_fn(params, batch, t)
        specs_out.update(t.specs)
        return losses.sum(), (losses, t.records)

    dEps, (losses, records) = jax.grad(f, has_aux=True)(eps0)
    return dEps, records, specs_out, losses


def ghost_norms(loss_fn, params, batch):
    """Per-example grad sq-norms via the ghost trick (no per-example grads)."""
    with phase("grad/norm_pass"):
        dEps, records, specs, losses = _eps_backward(loss_fn, params, batch)
        sq = jnp.zeros(losses.shape[0], jnp.float32)
        for name, spec in specs.items():
            rec = layers.resolve_record(records, name, spec)
            sq = sq + layers.per_example_sq_norm(spec, rec, dEps[name])
    return sq, losses


@register_engine("masked_ghost", record_based=True)
def ghost_clipped_grads(loss_fn: Callable, params, batch, mask,
                        clip_norm: float, *,
                        constraints: Optional[ShardingConstraints] = None
                        ) -> Tuple[dict, Aux]:
    """Ghost clipping: norm pass + reweighted second backward."""
    sq, _ = ghost_norms(loss_fn, params, batch)
    coef, norms = clip_coef(sq, mask, clip_norm)
    coef = jax.lax.stop_gradient(coef)

    def reweighted(p):
        losses = loss_fn(p, batch, Tape())
        return jnp.sum(coef * losses)

    with phase("grad/reweighted"):
        summed = jax.grad(reweighted)(params)
    with phase("grad/accumulate"):
        summed = jax.tree.map(lambda g: g.astype(jnp.float32), summed)
    return summed, {"per_example_norms": norms, "clip_coef": coef}


@register_engine("masked_bk", record_based=True)
def bk_clipped_grads(loss_fn: Callable, params, batch, mask,
                     clip_norm: float, check_coverage: bool = False, *,
                     constraints: Optional[ShardingConstraints] = None
                     ) -> Tuple[dict, Aux]:
    """Book-Keeping: one backward pass; clipped grads rebuilt from the tape."""
    dEps, records, specs, losses = _eps_backward(loss_fn, params, batch)
    sq = jnp.zeros(losses.shape[0], jnp.float32)
    for name, spec in specs.items():
        rec = layers.resolve_record(records, name, spec)
        sq = sq + layers.per_example_sq_norm(spec, rec, dEps[name])
    coef, norms = clip_coef(sq, mask, clip_norm)

    flat: Dict[str, jnp.ndarray] = {}
    for name, spec in specs.items():
        rec = layers.resolve_record(records, name, spec)
        for path, g in layers.bk_grads(spec, rec, dEps[name], coef).items():
            flat[path] = flat.get(path, 0.0) + g
    # dense param_path convention: '<path>.w' / '<path>.b' refer to leaves.
    if check_coverage:
        miss = missing_paths(flat, params)
        if miss:
            raise ValueError(f"BK grads missing for params: {miss}")
    summed = grads_into_tree(flat, params)
    return summed, {"per_example_norms": norms, "clip_coef": coef}
