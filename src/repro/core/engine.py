"""The DP step builders: DP-SGD steps with virtual batching (Algorithms 1 & 2).

Step anatomy (paper Alg. 2 / Opacus BatchMemoryManager semantics):

  * ``accumulate``: process ONE fixed-size physical batch — per-example clip
    (by the configured engine) with the Poisson 0/1 mask, add into grad_acc.
  * ``update``: once per logical batch — add N(0, (σC)²) noise, divide by the
    *expected* logical batch size L, apply the optimizer, reset grad_acc.
  * ``fused_step``: accumulate(+optional microbatch scan) + update in one jit —
    the unit that is lowered in the multi-pod dry-run and rooflined.

``TrainState.grad_acc`` is ONE flat f32 buffer (layout:
:class:`~repro.utils.params.FlatGradView`), not a per-leaf pytree:
``accumulate`` scatters the clipped sum into it once, and for SGD/momentum
``update`` dispatches to the fused :func:`repro.kernels.tree_noisy_update` —
noise + rescale + optimizer apply in one pass, one read+write of
params/acc/momentum per step (paper Table 2's DP-optimizer overhead is
exactly the extra passes this removes).  Adam-family optimizers take the
generic path on a lazily-unflattened tree view of the same buffer.

All step functions are pure; the host-side lifecycle (sampler, memory
manager, accountant, checkpointing) is owned by
:class:`repro.core.session.PrivacySession`, which is the supported entry
point.  The ``build_*`` factories here take sharding constraints explicitly
(:class:`~repro.core.clipping.ShardingConstraints`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..analysis.marks import mark as dp_mark, mark_tree as dp_mark_tree
from ..kernels import tree_noisy_update
from ..optim import Optimizer
from ..utils.params import FlatGradView
from . import clipping
from .clipping import ShardingConstraints
from .tape import Tape


@dataclasses.dataclass(frozen=True)
class DPConfig:
    clip_norm: float = 1.0
    noise_multiplier: float = 1.0        # sigma
    expected_batch_size: float = 64.0    # L = q * N
    engine: str = "masked_pe"            # pe|masked_pe|masked_fused|masked_fused_stream|masked_ghost|masked_bk|nonprivate
    microbatches: int = 1                # in-step grad accumulation (lax.scan)
    stream_tile: Optional[int] = None    # streaming engines: examples per
    #                                      scanned tile m; None = sized from
    #                                      the memory budget (costmodel rule)

    @property
    def private(self) -> bool:
        return self.engine != "nonprivate"

    def validate(self) -> "DPConfig":
        """Raise (with the registered-engine list) on an unknown engine."""
        if self.private:
            clipping.resolve_engine(self.engine)
        return self


def _grad_hook(constraints: Optional[ShardingConstraints]):
    return constraints.grad if constraints is not None else None


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    grad_acc: Any         # flat f32 (D,) buffer — FlatGradView(params) layout
    rng: jax.Array
    step: jax.Array       # optimizer steps taken
    seen: jax.Array       # f32 masked examples accumulated since last update


def fused_sgd(optimizer: Optimizer) -> bool:
    """True when the optimizer's update is the fused single-pass kernel
    (plain/momentum SGD); nesterov and Adam-family go through the generic
    ``optimizer.update`` on a tree view of the flat accumulator."""
    return (optimizer.kind == "sgd" and isinstance(optimizer.hyper, dict)
            and not optimizer.hyper.get("nesterov", False))


def init_state(params, optimizer: Optimizer, rng) -> TrainState:
    view = FlatGradView.for_tree(params)
    opt_state = optimizer.init(params)
    if (fused_sgd(optimizer) and isinstance(opt_state, dict)
            and opt_state.get("mom") is not None):
        # momentum lives in the same flat layout as grad_acc, so the fused
        # update reads/writes it in the one pass
        opt_state = dict(opt_state, mom=view.zeros())
    return TrainState(
        params=params,
        opt_state=opt_state,
        grad_acc=view.zeros(),
        rng=rng,
        step=jnp.zeros((), jnp.int32),
        seen=jnp.zeros((), jnp.float32),
    )


def _clipped_sum(loss_fn, params, batch, mask, cfg: DPConfig,
                 constraints: Optional[ShardingConstraints]):
    fn = clipping.resolve_engine(cfg.engine)
    return fn(loss_fn, params, batch, mask, cfg.clip_norm,
              constraints=constraints)


def _microbatched_clipped_sum(loss_fn, params, batch, mask, cfg: DPConfig,
                              constraints: Optional[ShardingConstraints]):
    """Split the physical batch into cfg.microbatches chunks and accumulate
    sequentially inside the step (keeps activation/record liveness bounded for
    the 67B/90B dry-runs — the in-jit analogue of virtual batching)."""
    if cfg.microbatches <= 1:
        return _clipped_sum(loss_fn, params, batch, mask, cfg, constraints)
    m = cfg.microbatches
    grad_constraint = _grad_hook(constraints)

    def resh(x):
        return x.reshape((m, x.shape[0] // m) + x.shape[1:])

    mb = jax.tree.map(resh, batch)
    mmask = resh(mask)

    def body(acc, xs):
        b, mk = xs
        g, aux = _clipped_sum(loss_fn, params, b, mk, cfg, constraints)
        if grad_constraint is not None:
            g = grad_constraint(g)
        acc = jax.tree.map(jnp.add, acc, g)
        return acc, (aux["per_example_norms"], aux["clip_coef"])

    acc0 = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    acc, (norms, coefs) = jax.lax.scan(body, acc0, (mb, mmask))
    return acc, {"per_example_norms": norms.reshape(-1),
                 "clip_coef": coefs.reshape(-1)}


def _stream_tile(state: TrainState, batch_size: int, view: FlatGradView,
                 constraints: Optional[ShardingConstraints]) -> int:
    """The streaming tile from the memory budget, net of the train state:
    the step's input state stays live beside its output (the local
    executor does not donate), so both count against the device."""
    from ..launch.costmodel import stream_tile_size
    state_bytes = 2 * sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(state))
    pe_dtype = constraints.pe_dtype if constraints is not None else None
    return stream_tile_size(
        batch_size, view.n_params, state_bytes=state_bytes,
        pe_dtype_bytes=jnp.dtype(pe_dtype or jnp.float32).itemsize)


def build_accumulate_fn(loss_fn: Callable, cfg: DPConfig, *,
                        constraints: Optional[ShardingConstraints] = None):
    """accumulate(state, batch, mask) -> (state, metrics). Jit-stable shapes."""
    streaming = (cfg.private and
                 getattr(clipping.resolve_engine(cfg.engine), "streaming",
                         False))
    if streaming and cfg.microbatches > 1:
        raise ValueError(
            f"engine {cfg.engine!r} streams tile-by-tile into the flat "
            f"accumulator; the stream_tile IS the in-step microbatch, so "
            f"cfg.microbatches must stay 1 (got {cfg.microbatches})")

    def _dp_metrics(aux, mask):
        """Batch-AGGREGATED step telemetry from the engine aux.  Every value
        reduces over the example axis before it leaves the step (masked mean
        / max / fraction), so the metrics outputs carry no per-example dim —
        the invariant the taint verifier's per-example-output rule and the
        L005 lint both enforce on observability taps."""
        norms = aux["per_example_norms"]
        seen = jnp.maximum(mask.sum(), 1)
        return {
            "mean_grad_norm": (norms * mask).sum() / seen,
            "max_grad_norm": (norms * mask).max(),
            # fraction of real (masked-in) examples whose grad was clipped
            "clip_fraction": ((norms > cfg.clip_norm) * mask).sum() / seen,
        }

    def accumulate(state: TrainState, batch, mask):
        # seen handling is normalised to f32 HERE, once: integer Poisson
        # masks otherwise accumulate an int `seen` that the nonprivate
        # update's f32 reset would retrace against
        mask = mask.astype(jnp.float32)
        view = FlatGradView.for_tree(state.params)
        grad_constraint = _grad_hook(constraints)
        if streaming:
            # the engine adds straight into the flat accumulator (aliased
            # Pallas kernel inside a scan) — no summed gradient tree, no
            # view.flatten scatter
            fn = clipping.resolve_engine(cfg.engine)
            tile = cfg.stream_tile or _stream_tile(state, mask.shape[0], view,
                                                   constraints)
            acc, aux = fn(loss_fn, state.params, batch, mask, cfg.clip_norm,
                          constraints=constraints, acc=state.grad_acc,
                          view=view, tile=tile)
            if constraints is not None and constraints.grad_flat is not None:
                acc = constraints.grad_flat(acc)
            metrics = _dp_metrics(aux, mask)
            return state._replace(grad_acc=acc,
                                  seen=state.seen + mask.sum()), metrics
        if cfg.private:
            g, aux = _microbatched_clipped_sum(loss_fn, state.params, batch,
                                               mask, cfg, constraints)
            metrics = _dp_metrics(aux, mask)
        else:
            # accumulate the masked SUM of per-example losses directly: the
            # update divides once by the total seen count, so every example
            # carries equal weight regardless of how mask counts split
            # across physical batches.
            def sum_loss(p):
                losses = loss_fn(p, batch, Tape())
                return (losses * mask).sum()
            g = jax.grad(sum_loss)(state.params)
            g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
            metrics = {}
        if grad_constraint is not None:
            g = grad_constraint(g)
        # ONE scatter of the clipped sum into the flat accumulator (the
        # concat fuses with the producers — no per-leaf buffer round-trip)
        acc = state.grad_acc + view.flatten(g)
        if constraints is not None and constraints.grad_flat is not None:
            acc = constraints.grad_flat(acc)
        return state._replace(grad_acc=acc, seen=state.seen + mask.sum()), metrics

    return accumulate


def build_update_fn(optimizer: Optimizer, cfg: DPConfig, *, fuse: bool = True,
                    constraints: Optional[ShardingConstraints] = None):
    """update(state) -> state. Noise + optimizer step + reset accumulator.

    SGD/momentum dispatches to the fused
    :func:`repro.kernels.tree_noisy_update` (noise generated and applied in
    one pass over the flat accumulator); other optimizers — and ``fuse=False``,
    the benchmark's multi-pass baseline — materialise the noisy gradient tree
    and run the generic ``optimizer.update``.  ``constraints.kernel_map``,
    when set, runs the update kernels on every device of the mesh.
    """
    kernel_map = constraints.kernel_map if constraints is not None else None

    def update(state: TrainState):
        view = FlatGradView.for_tree(state.params)
        rng, nkey = jax.random.split(state.rng)
        sigma_c = cfg.noise_multiplier * cfg.clip_norm

        if fuse and fused_sgd(optimizer):
            hyper = optimizer.hyper
            count = state.opt_state["count"]
            lr = hyper["lr"](count)
            if cfg.private:
                key, denom = nkey, cfg.expected_batch_size
            else:
                key, denom = None, jnp.maximum(state.seen, 1.0)
            params, new_mom = tree_noisy_update(
                state.params, state.grad_acc, key, sigma_c, denom, lr,
                momentum_buf=state.opt_state.get("mom"),
                momentum=hyper["momentum"], view=view, kernel_map=kernel_map)
            opt_state = dict(state.opt_state, count=count + 1)
            if new_mom is not None:
                opt_state["mom"] = new_mom
        else:
            # generic path: lazy tree view of the flat accumulator (+ flat
            # noise — the SAME stream the fused path draws, so both paths
            # produce identical updates for identical keys)
            if cfg.private:
                z = dp_mark("noise", view.noise(nkey), scale=sigma_c)
                g_flat = (state.grad_acc + sigma_c * z) \
                    / cfg.expected_batch_size
            else:
                g_flat = state.grad_acc / jnp.maximum(state.seen, 1.0)
            g = view.unflatten(g_flat)
            opt_in = state.opt_state
            # a fusable-SGD state stores momentum flat; present the generic
            # optimizer a tree view and restore the flat layout after
            mom_flat = (fused_sgd(optimizer) and isinstance(opt_in, dict)
                        and opt_in.get("mom") is not None)
            if mom_flat:
                opt_in = dict(opt_in, mom=view.unflatten(opt_in["mom"]))
            updates, opt_state = optimizer.update(g, opt_in, state.params)
            if mom_flat:
                opt_state = dict(opt_state, mom=view.flatten(opt_state["mom"]))
            params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                                  state.params, updates)
        # the updated params are what leaves the DP boundary — declare the
        # release so the verifier checks clipped+noised-exactly-once HERE
        params = dp_mark_tree("release", params)
        return TrainState(params, opt_state, view.zeros(), rng,
                          state.step + 1, jnp.zeros((), jnp.float32))

    return update


def build_fused_step(loss_fn: Callable, optimizer: Optimizer, cfg: DPConfig, *,
                     constraints: Optional[ShardingConstraints] = None):
    """One logical batch == one call: clip+accumulate then noise+update.
    This is the function lowered in the dry-run."""
    accumulate = build_accumulate_fn(loss_fn, cfg, constraints=constraints)
    update = build_update_fn(optimizer, cfg, constraints=constraints)

    def step(state: TrainState, batch, mask):
        state, metrics = accumulate(state, batch, mask)
        state = update(state)
        return state, metrics

    return step


def build_eval_fn(loss_fn: Callable):
    def evaluate(params, batch, mask):
        losses = loss_fn(params, batch, Tape())
        return (losses * mask).sum() / jnp.maximum(mask.sum(), 1)
    return evaluate
