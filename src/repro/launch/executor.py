"""Executor layer: ONE sharded execution path for fit / dryrun / serve.

The paper's headline scaling result (DP-SGD scales *better* than SGD) is an
execution-layer property, so execution must not fork: the same mesh
construction, sharding resolution, jit in/out-sharding + donation choices and
host->device placement serve every consumer.  An :class:`Executor` owns all
of that; everything else (``PrivacySession``, ``launch/dryrun``,
``launch/train``, ``launch/serve``, benchmarks) asks it to compile and place.

  * :class:`LocalExecutor` — single-process jit, host arrays moved with
    ``jnp.asarray``.  The default, and exactly what ``session.fit()`` did
    before this layer existed.
  * :class:`MeshExecutor` — a named-axis device mesh.  Resolves
    :class:`~repro.core.clipping.ShardingConstraints` for the configured
    layout, computes TrainState / batch / params / cache shardings, jits with
    ``out_shardings`` (+ donation off-CPU), and ``device_put``s every physical
    batch to its batch sharding.  Also exposes the AOT ``lower_*`` entry
    points the multi-pod dry-run records come from — lowering goes through
    the same code path that executes.

Select one with :class:`LaunchConfig`::

    LaunchConfig()                        # local, unsharded
    LaunchConfig(mesh="test")             # 2x2 host-device mesh (CPU tests)
    LaunchConfig(mesh="production")       # 16x16 = 256 chips, one pod
    LaunchConfig(mesh=(2, 16, 16))        # explicit shape; axes inferred
    LaunchConfig(mesh="test", layout="2d")  # FSDP+TP instead of pure DP

Layouts (mirroring the dry-run's ``--layout``):

  * ``dp``    — params replicated, batch over every mesh axis (the paper §7
                DDP setting; the layout ``fit()`` runs sharded).
  * ``dp_sp`` — params replicated, batch over non-'model' axes (sequence
                parallelism claims 'model').
  * ``2d``    — params FSDP over 'data' + tensor parallel over 'model',
                batch over the data axes; per-example/summed grads pinned.

Determinism note: a :class:`MeshExecutor` ``fit()`` in the ``dp`` layout
matches :class:`LocalExecutor` to reduction-order ULPs (~1e-9) and spends a
bit-identical eps.  Strict bitwise param equality across partitionings is not
achievable on XLA:CPU — LLVM contracts mul+add chains into FMAs per fusion,
so the same clipped-gradient sum rounds differently depending on how the
batch axis is split (verified empirically; ``optimization_barrier`` does not
survive lowering on this backend).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.sharding import (batch_pspec, cache_shardings,
                              flat_grads_constraint, grads_constraint,
                              params_shardings, pe_grads_constraint,
                              state_shardings)
from . import mesh as mesh_mod
from .mesh import make_mesh

# NOTE: repro.core is imported lazily where needed — core.session imports
# this module, so a top-level import would be circular.


def _engine_traits(engine: str):
    """(materializes_pe, record_based) from the engine registry — the engine
    definition owns this knowledge (see register_engine), not the executor."""
    if engine == "nonprivate":
        return False, False
    from ..core.clipping import resolve_engine
    fn = resolve_engine(engine)
    return (getattr(fn, "materializes_pe", False),
            getattr(fn, "record_based", False))


MESH_PRESETS = {
    "local": None,
    "test": ((2, 2), ("data", "model")),
    "production": mesh_mod.POD_SHAPE,
    "production-multipod": mesh_mod.MULTIPOD_SHAPE,
}
_DEFAULT_AXES = {1: ("data",), 2: ("data", "model"),
                 3: ("pod", "data", "model")}
LAYOUTS = ("dp", "dp_sp", "2d")


@dataclasses.dataclass(frozen=True)
class LaunchConfig:
    """Where and how a session executes: mesh (preset name or shape), axis
    names, layout, and per-example-grad storage dtype."""
    mesh: Union[str, Tuple[int, ...], None] = None   # None/"local" => local
    axes: Optional[Tuple[str, ...]] = None           # for tuple mesh shapes
    layout: str = "dp"                               # dp | dp_sp | 2d
    pe_bf16: bool = False                            # store pe grads in bf16

    def validate(self) -> "LaunchConfig":
        if self.layout not in LAYOUTS:
            raise ValueError(f"Unknown layout {self.layout!r}; "
                             f"expected one of {LAYOUTS}")
        self.resolved()
        return self

    def resolved(self) -> Tuple[Optional[Tuple[int, ...]], Optional[Tuple[str, ...]]]:
        """(mesh shape, axis names) — (None, None) means local execution."""
        mesh = self.mesh
        if mesh is None:
            return None, None
        if isinstance(mesh, str):
            if mesh not in MESH_PRESETS:
                raise ValueError(
                    f"Unknown mesh preset {mesh!r}; expected one of "
                    f"{sorted(MESH_PRESETS)} or an explicit shape tuple.")
            preset = MESH_PRESETS[mesh]
            if preset is None:
                return None, None
            return preset
        shape = tuple(int(s) for s in mesh)
        axes = self.axes if self.axes is not None else _DEFAULT_AXES.get(len(shape))
        if axes is None or len(axes) != len(shape):
            raise ValueError(
                f"mesh shape {shape} needs {len(shape)} axis names; got "
                f"axes={self.axes!r} (defaults exist for 1-3 axes).")
        return shape, tuple(axes)

    @property
    def is_local(self) -> bool:
        return self.resolved()[0] is None

    def mesh_shape(self) -> Optional[dict]:
        """axis -> size, WITHOUT touching jax device state (cost models use
        this to describe meshes far larger than the host)."""
        shape, axes = self.resolved()
        if shape is None:
            return None
        return dict(zip(axes, shape))

    def build_mesh(self) -> Optional[Mesh]:
        shape, axes = self.resolved()
        if shape is None:
            return None
        need, have = math.prod(shape), len(jax.devices())
        if have < need:
            raise RuntimeError(
                f"mesh {dict(zip(axes, shape))} needs {need} devices but jax "
                f"initialised {have}. On CPU, set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need} before the "
                f"first jax call (launch.dryrun sets this automatically only "
                f"when XLA_FLAGS does not already pin a device count).")
        return make_mesh(shape, axes)


class Executor:
    """Compiles step functions and owns array placement.  Subclasses decide
    shardings; callers never touch jax.jit / device_put directly."""

    mesh: Optional[Mesh] = None

    # -- sharding resolution ------------------------------------------------

    def constraints(self, engine: str) -> "ShardingConstraints":
        from ..core.clipping import ShardingConstraints
        return ShardingConstraints()

    # -- jit ---------------------------------------------------------------

    def jit_step(self, fn: Callable, state_shape) -> Callable:
        """(state, batch, mask) -> (state, metrics)."""
        raise NotImplementedError

    def jit_update(self, fn: Callable, state_shape) -> Callable:
        """(state,) -> state."""
        raise NotImplementedError

    def jit_eval(self, fn: Callable) -> Callable:
        """(params, batch, mask) -> scalar."""
        return jax.jit(fn)

    def jit_decode(self, fn: Callable) -> Callable:
        """(params, cache, tokens, pos) -> (logits, cache)."""
        return jax.jit(fn)

    def jit_prefill_step(self, fn: Callable) -> Callable:
        """(params, cache, tokens (B,C), pos (B,), n_tok (B,)) ->
        (logits, cache) — the chunked-prefill entry point beside
        jit_decode (see repro.serve.scheduler)."""
        return jax.jit(fn)

    # -- placement ---------------------------------------------------------

    def place_state(self, state):
        return state

    def place_batch(self, batch):
        return jax.tree.map(jnp.asarray, batch)

    def place_mask(self, mask):
        return jnp.asarray(mask)

    def place(self, batch, mask):
        """One physical batch -> device.  The BatchMemoryManager placement
        hook, so host->device transfer happens as batches are produced."""
        return self.place_batch(batch), self.place_mask(mask)

    def place_cache(self, cache, batch_size: int):
        return cache

    def configure_model(self, cfg, kind: str, seq_len: int,
                        global_batch: int, engine: str) -> None:
        """Install the model-level activation/expert sharding hints for the
        program about to be traced (process-wide hooks in models.common).
        Local execution clears them; mesh execution resolves them from the
        layout — so a session traces the SAME program the dry-run lowers."""
        from ..models import common as cm
        cm.set_act_sharding(None)
        cm.set_expert_sharding(None)

    # -- AOT tracing (the static-verifier seam, sibling of lower_train) -----

    def trace_train(self, step_fn, state_shape, batch_specs, mask_spec):
        """(ClosedJaxpr, out_info) of the train step exactly as this executor
        would jit it — consumed by :mod:`repro.analysis`."""
        traced = jax.jit(step_fn).trace(state_shape, batch_specs, mask_spec)
        return traced.jaxpr, traced.out_info

    def describe(self) -> dict:
        raise NotImplementedError


class LocalExecutor(Executor):
    """Single-process execution — plain jit, arrays wherever jax puts them.
    Honors the LaunchConfig fields that are meaningful unsharded (pe_bf16);
    layout only exists once there is a mesh."""

    def __init__(self, launch: Optional[LaunchConfig] = None):
        self.launch = launch if launch is not None else LaunchConfig()

    def constraints(self, engine: str) -> "ShardingConstraints":
        from ..core.clipping import ShardingConstraints
        return ShardingConstraints(
            pe_dtype=jnp.bfloat16 if self.launch.pe_bf16 else None)

    def jit_step(self, fn, state_shape):
        return jax.jit(fn)

    def jit_update(self, fn, state_shape):
        return jax.jit(fn)

    def describe(self) -> dict:
        return {"executor": "local"}


class MeshExecutor(Executor):
    """Execution on a named-axis device mesh.

    All sharding policy lives here: TrainState via
    :func:`~repro.utils.sharding.state_shardings` (2d) or replicated (dp),
    batches via :func:`~repro.utils.sharding.batch_pspec`, params/caches via
    their ``utils.sharding`` rules, gradient pins via
    :func:`~repro.utils.sharding.grads_constraint` /
    :func:`~repro.utils.sharding.pe_grads_constraint`.
    """

    def __init__(self, launch: LaunchConfig):
        launch.validate()
        if launch.is_local:
            raise ValueError("LaunchConfig resolves to local execution; "
                             "use LocalExecutor (via build_executor).")
        self.launch = launch
        self.layout = launch.layout
        unknown = [a for a in launch.resolved()[1]
                   if a not in ("pod", "data", "model")]
        if unknown:
            raise ValueError(
                f"MeshExecutor's sharding rules know the axes "
                f"('pod', 'data', 'model'); got unknown axes {unknown}. "
                f"Name the LaunchConfig axes accordingly — arbitrary names "
                f"are only for LaunchConfig.mesh_shape() cost descriptions.")
        self.mesh = launch.build_mesh()
        self._replicated = NamedSharding(self.mesh, P())

    # -- sharding resolution ------------------------------------------------

    def constraints(self, engine: str) -> "ShardingConstraints":
        from ..core.clipping import ShardingConstraints
        pe_dtype = jnp.bfloat16 if self.launch.pe_bf16 else None
        if self.layout in ("dp", "dp_sp"):
            # fully replicated state: GSPMD needs no layout pins.  The flat
            # accumulator stays REPLICATED here on purpose: forcing it to
            # the offset-range layout makes XLA:CPU's SPMD partitioner
            # produce values ~1e-2 off the replicated program (not
            # reduction-order ULPs — same backend bug class as the rope
            # reshard in utils/sharding.cache_pspec), which would break the
            # dp/dp_sp fit()==local parity contract.  Under 2d that exact
            # parity was never on offer (params themselves reshard), so the
            # memory win is taken there.
            return ShardingConstraints(pe_dtype=pe_dtype,
                                       tile_batch=self._tile_constraint(),
                                       kernel_map=self._kernel_map)
        return ShardingConstraints(
            grad=grads_constraint(self.mesh),
            grad_flat=flat_grads_constraint(self.mesh),
            pe_grad=(pe_grads_constraint(self.mesh)
                     if _engine_traits(engine)[0] else None),
            pe_dtype=pe_dtype,
            tile_batch=self._tile_constraint())

    def _tile_constraint(self):
        """Streaming-engine hook: pin each scanned microbatch tile (batch
        leaves + mask) to the SAME data axes the incoming batch is sharded
        over, so the per-tile backward shards like the full-batch one and no
        per-iteration reshard creeps into the scan body.  ``batch_spec``
        falls back to replication when the tile doesn't divide the axes."""
        def apply(tree):
            def one(x):
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(self.mesh, self.batch_spec(x.shape[0])))
            return jax.tree.map(one, tree)
        return apply

    def _kernel_map(self, fn: Callable, rows: int = 0) -> Callable:
        """The ``ShardingConstraints.kernel_map`` of the replicated-state
        layouts: ``fn`` runs under ``shard_map`` on each device, the first
        ``rows`` arguments split like a batch of their leading size (or
        replicated when that does not divide the data axes), and per-device
        results summed over the axes the rows were split on."""
        def call(*args):
            spec = (self.batch_spec(args[0].shape[0]) if rows else P())
            axes = spec[0] if len(spec) else ()
            axes = (axes,) if isinstance(axes, str) else tuple(axes or ())

            def body(*a):
                out = fn(*a)
                return jax.lax.psum(out, axes) if axes else out
            in_specs = tuple(spec if i < rows else P()
                             for i in range(len(args)))
            return jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                                 out_specs=P(), check_vma=False)(*args)
        return call

    def batch_spec(self, bsz: int) -> P:
        if self.layout in ("dp", "dp_sp"):
            axes = tuple(a for a in self.mesh.shape
                         if not (self.layout == "dp_sp" and a == "model"))
            if bsz % math.prod(self.mesh.shape[a] for a in axes) == 0:
                return P(axes)
        return batch_pspec(self.mesh, bsz)

    def batch_sharding(self, bsz: int) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec(bsz))

    def state_sharding(self, state_shape):
        if self.layout in ("dp", "dp_sp"):
            # fully replicated, including the flat accumulator — see the
            # constraints() comment for why it is NOT offset-range-sharded
            # in these layouts
            return jax.tree.map(lambda _: self._replicated, state_shape)
        return state_shardings(state_shape, self.mesh)

    def _donate(self, argnums: Tuple[int, ...]) -> Tuple[int, ...]:
        # donation is unimplemented on the CPU backend (warns per call site)
        return argnums if jax.default_backend() != "cpu" else ()

    # -- jit (shardings inferred from placed args; outputs pinned) ----------

    def jit_step(self, fn, state_shape):
        sshard = self.state_sharding(state_shape)
        return jax.jit(fn, out_shardings=(sshard, None),
                       donate_argnums=self._donate((0,)))

    def jit_update(self, fn, state_shape):
        sshard = self.state_sharding(state_shape)
        return jax.jit(fn, out_shardings=sshard,
                       donate_argnums=self._donate((0,)))

    def jit_decode(self, fn):
        return jax.jit(fn, donate_argnums=self._donate((1,)))

    def jit_prefill_step(self, fn):
        return jax.jit(fn, donate_argnums=self._donate((1,)))

    # -- placement ---------------------------------------------------------

    def place_state(self, state):
        sshard = self.state_sharding(jax.eval_shape(lambda: state))
        return jax.device_put(state, sshard)

    def place_batch(self, batch):
        # device_put takes host arrays directly — one transfer straight to
        # the sharded layout, no intermediate default-device copy
        bsz = jax.tree.leaves(batch)[0].shape[0]
        spec = self.batch_sharding(bsz)
        return jax.tree.map(lambda x: jax.device_put(x, spec), batch)

    def place_mask(self, mask):
        return jax.device_put(mask, self.batch_sharding(len(mask)))

    def place_cache(self, cache, batch_size: int):
        cshard = cache_shardings(jax.eval_shape(lambda: cache), self.mesh,
                                 batch_size)
        return jax.device_put(cache, cshard)

    def describe(self) -> dict:
        return {"executor": "mesh", "mesh": dict(self.mesh.shape),
                "layout": self.layout}

    # -- model-level activation sharding hints ------------------------------

    def act_sharding_spec(self, seq_len: int, global_batch: int,
                          kind: str, engine: str) -> Optional[P]:
        """Sequence-parallel activation spec over 'model' when the layout and
        shape allow it (block activations — and hence ghost records / eps /
        dY buffers — become T-sharded), else None."""
        if "model" not in self.mesh.shape:
            return None
        seq_par_ok = (self.layout in ("2d", "dp_sp") and
                      (kind == "prefill" or
                       (kind == "train" and _engine_traits(engine)[1])))
        if not (seq_par_ok and seq_len % self.mesh.shape["model"] == 0):
            return None
        bp = self.batch_spec(global_batch)
        bax = bp[0] if len(bp) else None
        return P(bax, "model", None)

    def expert_sharding_spec(self, n_experts: int,
                             global_batch: int) -> Optional[P]:
        """Expert-parallel dispatch-buffer spec (E, B, cap, D) for MoE archs
        under the 2d layout."""
        if not (n_experts and self.layout == "2d"
                and "model" in self.mesh.shape):
            return None
        bp = self.batch_spec(global_batch)
        bax = bp[0] if len(bp) else None
        return P("model", bax, None, None)

    def configure_model(self, cfg, kind: str, seq_len: int,
                        global_batch: int, engine: str) -> None:
        from ..models import common as cm
        # hand the hooks NamedShardings (mesh baked in), not bare
        # PartitionSpecs: executed jits have no `with mesh:` context
        act = self.act_sharding_spec(seq_len, global_batch, kind, engine)
        cm.set_act_sharding(
            NamedSharding(self.mesh, act) if act is not None else None)
        exp = self.expert_sharding_spec(getattr(cfg, "n_experts", 0),
                                        global_batch)
        cm.set_expert_sharding(
            NamedSharding(self.mesh, exp) if exp is not None else None)

    # -- AOT lowering (the dry-run path; donation is fine for AOT) ----------

    def lower_train(self, step_fn, state_shape, batch_specs, mask_spec):
        sshard = self.state_sharding(state_shape)
        bspec = self.batch_sharding(mask_spec.shape[0])
        bshard = jax.tree.map(lambda _: bspec, batch_specs)
        with self.mesh:
            return jax.jit(
                step_fn, in_shardings=(sshard, bshard, bspec),
                out_shardings=(sshard, None),
                donate_argnums=(0,)).lower(state_shape, batch_specs,
                                           mask_spec)

    def trace_train(self, step_fn, state_shape, batch_specs, mask_spec):
        """Same jit construction as :meth:`lower_train` (shardings + donation),
        stopped at the traced jaxpr — what the verifier interprets is the
        program this mesh would run."""
        sshard = self.state_sharding(state_shape)
        bspec = self.batch_sharding(mask_spec.shape[0])
        bshard = jax.tree.map(lambda _: bspec, batch_specs)
        with self.mesh:
            traced = jax.jit(
                step_fn, in_shardings=(sshard, bshard, bspec),
                out_shardings=(sshard, None),
                donate_argnums=(0,)).trace(state_shape, batch_specs,
                                           mask_spec)
        return traced.jaxpr, traced.out_info

    def lower_prefill(self, fn, params_shape, batch_specs):
        pshard = params_shardings(params_shape, self.mesh)
        bsz = jax.tree.leaves(batch_specs)[0].shape[0]
        bspec = self.batch_sharding(bsz)
        bshard = jax.tree.map(lambda _: bspec, batch_specs)
        with self.mesh:
            return jax.jit(fn, in_shardings=(pshard, bshard),
                           out_shardings=bspec).lower(params_shape,
                                                      batch_specs)

    def lower_decode(self, fn, params_shape, cache_shape, tok_spec, pos_spec):
        pshard = params_shardings(params_shape, self.mesh)
        bsz = tok_spec.shape[0]
        cshard = cache_shardings(cache_shape, self.mesh, bsz)
        bspec = self.batch_sharding(bsz)
        with self.mesh:
            return jax.jit(
                fn, in_shardings=(pshard, cshard, bspec, self._replicated),
                out_shardings=(bspec, cshard),
                donate_argnums=(1,)).lower(params_shape, cache_shape,
                                           tok_spec, pos_spec)

    def lower_prefill_step(self, fn, params_shape, cache_shape, tok_spec,
                           pos_spec, ntok_spec):
        """AOT lowering of the chunked-prefill entry point — the same
        shardings as lower_decode with the (B, C) token chunk batched over
        the data axes and the per-slot pos/n_tok vectors replicated."""
        pshard = params_shardings(params_shape, self.mesh)
        bsz = tok_spec.shape[0]
        cshard = cache_shardings(cache_shape, self.mesh, bsz)
        bspec = self.batch_sharding(bsz)
        with self.mesh:
            return jax.jit(
                fn, in_shardings=(pshard, cshard, bspec, self._replicated,
                                  self._replicated),
                out_shardings=(bspec, cshard),
                donate_argnums=(1,)).lower(params_shape, cache_shape,
                                           tok_spec, pos_spec, ntok_spec)


def build_executor(launch: Optional[LaunchConfig]) -> Executor:
    """The one place an executor is chosen from a LaunchConfig."""
    launch = launch if launch is not None else LaunchConfig()
    launch.validate()          # local configs are validated too
    if launch.is_local:
        return LocalExecutor(launch)
    return MeshExecutor(launch)
