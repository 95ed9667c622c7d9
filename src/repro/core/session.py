"""PrivacySession: one object that owns the full DP-SGD lifecycle.

The paper's claim is that correct Poisson-subsampled DP-SGD is efficient when
the sampler, clipping engine, accountant and optimizer are engineered as one
coherent system; this module is that system's single entry point (the role
``PrivacyEngine`` plays in Opacus).  A session composes:

  * a sampler resolved from the decorator registry in
    :mod:`repro.data.sampler` (``TrainConfig.sampler``; the default
    ``poisson`` is proper Bernoulli(q) draws — the "no shortcuts"
    requirement — with ``balls_and_bins`` / ``shuffle`` / ``full_batch``
    as registered alternatives, each accounted under its own valid bound)
    and the :class:`~repro.data.BatchMemoryManager`
    (fixed physical shapes, so jit compiles exactly once),
  * a clipping engine resolved from the decorator registry in
    :mod:`repro.core.clipping` (unknown names fail listing what IS registered),
  * the RDP :class:`~repro.privacy.PrivacyAccountant`, with σ auto-calibrated
    from ``target_eps`` when requested,
  * the optimizer + LR schedule,
  * sharding constraints passed explicitly
    (:class:`~repro.core.clipping.ShardingConstraints`) instead of mutable
    module globals, and
  * an :class:`~repro.launch.executor.Executor` resolved from a
    :class:`~repro.launch.executor.LaunchConfig` — the single place mesh
    construction, jit shardings and host->device placement happen, shared
    with the dry-run and serving paths.  ``fit()`` runs sharded when the
    session is built with ``launch=LaunchConfig(mesh=...)``; "sharded DP-SGD"
    is a config value, not a separate script.

Quickstart::

    from repro.core.session import PrivacySession, TrainConfig
    from repro.core import DPConfig

    session = PrivacySession.from_config(
        "qwen2-0.5b",
        DPConfig(engine="masked_pe", clip_norm=1.0),
        TrainConfig(steps=4, n_data=256, q=0.25, target_eps=8.0))
    out = session.fit()
    print(session.privacy_spent(), session.describe())
"""
from __future__ import annotations

import dataclasses
import sys
import time
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..data import BatchMemoryManager, make_sampler
from ..data.sampler import SAMPLER_STREAM_VERSION
from ..launch.executor import LaunchConfig, build_executor
from ..obs import as_registry
from ..resilience.faults import fault_point
from ..privacy import PrivacyAccountant, calibrate_sigma
from ..privacy import rdp as rdp_mod
from ..optim import (Optimizer, adamw, constant, cosine,
                     linear_warmup_cosine, sgd)
from .clipping import ShardingConstraints
from .engine import (DPConfig, TrainState, build_accumulate_fn,
                     build_eval_fn, build_fused_step, build_update_fn,
                     init_state)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Host-side lifecycle knobs: data, sampling, optimizer, seeding."""
    steps: int = 4
    n_data: int = 512
    seq_len: int = 16
    physical_batch: int = 8
    q: float = 0.25                      # nominal sampling rate (L = q * N)
    sampler: str = "poisson"             # registered sampler name
    target_eps: Optional[float] = None   # auto-calibrate sigma when set
    delta: Optional[float] = None        # default: 1 / (10 * n_data)
    lr: float = 1e-3
    optimizer: str = "sgd"               # sgd | adamw
    momentum: float = 0.9                # sgd only
    weight_decay: float = 0.0            # adamw only
    schedule: str = "constant"           # constant | cosine | warmup_cosine
    warmup: int = 0
    smoke: bool = True                   # reduced model configs (CPU-friendly)
    seed: int = 0
    log_every: int = 1

    @property
    def resolved_delta(self) -> float:
        return self.delta if self.delta is not None else 1.0 / (10 * self.n_data)


def _build_schedule(tc: TrainConfig) -> Callable:
    if tc.schedule == "constant":
        return constant(tc.lr)
    if tc.schedule == "cosine":
        return cosine(tc.lr, tc.steps)
    if tc.schedule == "warmup_cosine":
        return linear_warmup_cosine(tc.lr, tc.warmup, tc.steps)
    raise ValueError(f"Unknown schedule {tc.schedule!r}; "
                     f"expected constant | cosine | warmup_cosine")


def _build_optimizer(tc: TrainConfig) -> Optimizer:
    sched = _build_schedule(tc)
    if tc.optimizer == "sgd":
        return sgd(sched, momentum=tc.momentum)
    if tc.optimizer == "adamw":
        return adamw(sched, weight_decay=tc.weight_decay)
    raise ValueError(f"Unknown optimizer {tc.optimizer!r}; "
                     f"expected sgd | adamw")


class PrivacySession:
    """The audited DP-SGD path every entry point goes through.

    Build one with :meth:`from_config` (arch name or ArchConfig), or directly
    from a model object.  All jit caching happens internally; the privacy
    accountant advances on every optimizer step the session takes.
    """

    def __init__(self, model, model_cfg, dp: DPConfig, train: TrainConfig, *,
                 optimizer: Optional[Optimizer] = None,
                 constraints: Optional[ShardingConstraints] = None,
                 accountant: Optional[PrivacyAccountant] = None,
                 loss_fn: Optional[Callable] = None,
                 launch: Optional[LaunchConfig] = None,
                 obs=None):
        dp.validate()                       # fail fast, listing the registry
        # resolve the sampler NOW (unknown names / bad (n, q) fail at
        # construction, listing the registry) and read back its EFFECTIVE
        # per-step participation rate — what the accountant must charge
        # (e.g. shuffle's batch_size/n, balls-and-bins' 1/bins, full's 1.0)
        self._sampler_q = float(make_sampler(
            train.sampler, n=train.n_data, q=train.q, seed=train.seed).q)
        self.model = model
        self.model_cfg = model_cfg
        self.dp = dp
        self.train_cfg = train
        self.launch = launch if launch is not None else LaunchConfig()
        # telemetry: None/off is a strict no-op registry (zero added sync
        # points on the step path); ObsConfig/MetricsRegistry turn on the
        # per-phase spans + DP gauges fit() and the serve engine emit
        self.obs = as_registry(obs)
        self.executor = build_executor(self.launch)
        self.constraints = constraints if constraints is not None \
            else self.executor.constraints(dp.engine)
        self.optimizer = optimizer if optimizer is not None \
            else _build_optimizer(train)
        self.accountant = accountant if accountant is not None \
            else PrivacyAccountant(delta=train.resolved_delta)
        self.loss_fn = loss_fn if loss_fn is not None \
            else (lambda p, b, t: model.loss(p, b, t))
        # model-level activation/expert sharding hints for the training
        # program — the same hooks the dry-run installs before lowering
        self.executor.configure_model(model_cfg, "train", train.seq_len,
                                      train.physical_batch, dp.engine)
        params = model.init(jax.random.PRNGKey(train.seed))
        self.state: TrainState = self.executor.place_state(init_state(
            params, self.optimizer, jax.random.PRNGKey(train.seed + 1)))
        self.restored_meta: Optional[dict] = None   # set by restore()
        self._jit_cache: dict = {}
        self._ckpt_writer = None                    # lazy AsyncCheckpointer

    # -- construction -------------------------------------------------------

    @classmethod
    def from_config(cls, model_cfg, dp_cfg: Optional[DPConfig] = None,
                    train_cfg: Optional[TrainConfig] = None, *,
                    constraints: Optional[ShardingConstraints] = None,
                    optimizer: Optional[Optimizer] = None,
                    launch: Optional[LaunchConfig] = None,
                    obs=None) -> "PrivacySession":
        """Build a session from (arch name | ArchConfig, DPConfig, TrainConfig).

        When ``train_cfg.target_eps`` is set and the engine is private, σ is
        calibrated so that ``train_cfg.steps`` steps at rate q spend at most
        target_eps at δ; ``dp_cfg.expected_batch_size`` is likewise derived
        from the sampler (L = q·N) so the config cannot disagree with the
        sampling that actually happens.  ``launch`` selects the executor:
        ``LaunchConfig(mesh="test")`` runs the same ``fit()`` sharded on a
        2x2 host-device mesh, ``mesh="production"`` on the 256-chip pod.
        """
        from ..models import build, build_by_name
        dp_cfg = dp_cfg if dp_cfg is not None else DPConfig()
        train_cfg = train_cfg if train_cfg is not None else TrainConfig()
        if isinstance(model_cfg, str):
            model, cfg = build_by_name(model_cfg, smoke=train_cfg.smoke)
        else:
            cfg = model_cfg.reduced() if (train_cfg.smoke and
                                          hasattr(model_cfg, "reduced")) \
                else model_cfg
            model = build(cfg)
        # the sampler probe pins L and the accounting rate to the sampling
        # that actually happens (shuffle rounds q*n to a batch size,
        # balls-and-bins rounds 1/q to a bin count, full_batch is q=1)
        probe = make_sampler(train_cfg.sampler, n=train_cfg.n_data,
                             q=train_cfg.q, seed=train_cfg.seed)
        L = probe.expected_batch_size
        if not dp_cfg.private:
            sigma = 0.0
        elif train_cfg.target_eps is not None:
            # calibrated under the bound VALID for this sampler: shortcut
            # samplers (unamplified accounting) get the larger sigma their
            # true cost demands instead of borrowing amplification
            sigma = calibrate_sigma(train_cfg.target_eps, probe.q,
                                    train_cfg.steps, train_cfg.resolved_delta,
                                    sampler=train_cfg.sampler)
        else:
            sigma = dp_cfg.noise_multiplier
        dp_cfg = dataclasses.replace(dp_cfg, noise_multiplier=sigma,
                                     expected_batch_size=L)
        return cls(model, cfg, dp_cfg, train_cfg,
                   optimizer=optimizer, constraints=constraints,
                   launch=launch, obs=obs)

    @classmethod
    def restore(cls, path: str, model_cfg, dp_cfg: Optional[DPConfig] = None,
                train_cfg: Optional[TrainConfig] = None, **kw) -> "PrivacySession":
        """from_config + load the full train state: params, optimizer state,
        the train-state RNG key and step/eps/accountant metadata.  Restoring
        opt state + RNG (not just params) is what makes a resumed ``fit()``
        bitwise-identical to the uninterrupted run — momentum buffers and
        the noise stream continue where they stopped."""
        from ..checkpoint import load as ckpt_load, unflatten_state
        from ..utils.params import flatten_params, unflatten_params
        session = cls.from_config(model_cfg, dp_cfg, train_cfg, **kw)
        snap = ckpt_load(path)
        tmpl = flatten_params(session.state.params)
        got = flatten_params(snap.params)
        params = unflatten_params(
            {k: np.asarray(got[k]).astype(v.dtype).reshape(v.shape)
             for k, v in tmpl.items()})
        step, meta = snap.step, snap.meta
        opt_state = session.state.opt_state
        if snap.opt_flat:
            try:
                opt_state = unflatten_state(snap.opt_flat, opt_state)
            except (KeyError, ValueError, TypeError) as e:
                warnings.warn(
                    f"checkpoint optimizer state does not match this "
                    f"session's optimizer ({e}); keeping freshly initialised "
                    f"opt state — the resumed run will NOT be bitwise "
                    f"identical to an uninterrupted one", RuntimeWarning,
                    stacklevel=2)
        rng = session.state.rng
        if "rng" in snap.extra:
            rng = jnp.asarray(np.asarray(snap.extra["rng"]).astype(
                np.asarray(rng).dtype).reshape(np.asarray(rng).shape))
        session.state = session.executor.place_state(session.state._replace(
            params=params, opt_state=opt_state, rng=rng,
            step=jnp.asarray(step, jnp.int32)))
        acc_state = (meta or {}).get("accountant")
        if acc_state is not None:
            # exact re-seat: the checkpoint carries the full (q, sigma,
            # steps, sampler) history, so restored eps is right even across
            # schedule or sampler changes
            session.accountant = PrivacyAccountant.from_state(acc_state)
        elif step and session.dp.private:
            # legacy checkpoint without accountant state: assume the
            # checkpointed steps were taken at this session's
            # (q, sigma, sampler)
            session.accountant.step(session._sampler_q,
                                    session.dp.noise_multiplier, steps=step,
                                    sampler=session.train_cfg.sampler)
        ck_sampler = (meta or {}).get("sampler", "poisson")
        if ck_sampler != session.train_cfg.sampler:
            warnings.warn(
                f"checkpoint was written by a {ck_sampler!r}-sampled run but "
                f"this session resumes with {session.train_cfg.sampler!r}: "
                f"the accountant history keeps the old steps' tags (eps "
                f"stays correct) but the executed sampling distribution "
                f"changes at the resume point", RuntimeWarning, stacklevel=2)
        ck_stream = int((meta or {}).get("sampler_stream_version", 1))
        if ck_stream != SAMPLER_STREAM_VERSION:
            warnings.warn(
                f"checkpoint's sampler streams are v{ck_stream} but this "
                f"code draws v{SAMPLER_STREAM_VERSION} (domain-separated "
                f"Philox keys): the resumed run's remaining draws come from "
                f"the new streams, so it is NOT bitwise comparable to an "
                f"uninterrupted v{ck_stream} run (the DP guarantee is "
                f"unaffected — the accountant charges what is executed)",
                RuntimeWarning, stacklevel=2)
        session.restored_meta = meta
        return session

    # -- jitted step functions (cached per session) -------------------------

    @property
    def step_fn(self):
        """The pure fused step (state, batch, mask) -> (state, metrics) —
        unjitted, for benchmarks that lower/compile it themselves."""
        if "raw_step" not in self._jit_cache:
            self._jit_cache["raw_step"] = build_fused_step(
                self.loss_fn, self.optimizer, self.dp,
                constraints=self.constraints)
        return self._jit_cache["raw_step"]

    def _jitted(self, name: str):
        """Step functions compiled BY THE EXECUTOR — the same jit/sharding
        decisions whether the session runs local or on a mesh."""
        if name not in self._jit_cache:
            ex = self.executor
            state_shape = jax.eval_shape(lambda: self.state)
            if name == "step":
                self._jit_cache[name] = ex.jit_step(self.step_fn, state_shape)
            elif name == "accumulate":
                self._jit_cache[name] = ex.jit_step(build_accumulate_fn(
                    self.loss_fn, self.dp, constraints=self.constraints),
                    state_shape)
            elif name == "update":
                self._jit_cache[name] = ex.jit_update(build_update_fn(
                    self.optimizer, self.dp, constraints=self.constraints),
                    state_shape)
            elif name == "evaluate":
                self._jit_cache[name] = ex.jit_eval(build_eval_fn(self.loss_fn))
            else:
                raise KeyError(name)
        return self._jit_cache[name]

    def compiled(self, name: str, batch=None, mask=None):
        """Ahead-of-time compile of one step program ("accumulate" and
        "step" for a physical ``batch``/``mask``, "update") exactly as
        ``fit()`` jits it.  Returns jax's ``Compiled``: its ``as_text()``
        and ``memory_analysis()`` describe the program ``fit()`` runs."""
        self._configure_train()
        fn = self._jitted(name)
        if name == "update":
            return fn.lower(self.state).compile()
        batch, mask = self.executor.place(batch, mask)
        return fn.lower(self.state, batch, mask).compile()

    # -- the DP-SGD lifecycle ----------------------------------------------

    @property
    def params(self):
        return self.state.params

    def _configure_train(self) -> None:
        """(Re)install the training-program model-sharding hints.  The hooks
        are process-wide and jits trace lazily (including shape-triggered
        retraces), so they are re-installed before every entry point that
        can trace the training program — generate() installs the decode
        program's hints the same way."""
        tc = self.train_cfg
        self.executor.configure_model(self.model_cfg, "train", tc.seq_len,
                                      tc.physical_batch, self.dp.engine)

    def step(self, batch, mask) -> dict:
        """One logical batch -> one optimizer step (clip + noise + update),
        advancing the privacy accountant."""
        self._configure_train()
        batch, mask = self.executor.place(batch, mask)
        self.state, metrics = self._jitted("step")(self.state, batch, mask)
        self._account()
        return metrics

    def accumulate(self, batch, mask) -> dict:
        """Clip-and-accumulate one physical batch (no optimizer step)."""
        self._configure_train()
        batch, mask = self.executor.place(batch, mask)
        self.state, metrics = self._jitted("accumulate")(self.state, batch,
                                                         mask)
        return metrics

    def update(self) -> None:
        """Noise + optimizer step over the accumulated logical batch."""
        self.state = self._jitted("update")(self.state)
        self._account()

    def _account(self) -> None:
        if self.dp.private:
            # charge the sampler's EFFECTIVE rate under its declared bound
            # (amplified vs unamplified) — never the nominal q
            self.accountant.step(self._sampler_q, self.dp.noise_multiplier,
                                 sampler=self.train_cfg.sampler)

    def _jit_entries(self) -> int:
        """Total compiled-program cache entries across the session's jitted
        step functions — the retrace counter.  Anything above one entry per
        cached function means a shape/dtype-triggered retrace (the guard
        tests/test_analysis.py pins at exactly one)."""
        total = 0
        for fn in self._jit_cache.values():
            size = getattr(fn, "_cache_size", None)
            if callable(size):
                total += int(size())
        return total

    def _record_step_telemetry(self, acc_metrics, step: int,
                               examples: int) -> None:
        """Per-step observability taps.  Host-side values (ε from the
        accountant, jit cache sizes, counters) are recorded on every tick;
        DEVICE scalars — the batch-aggregated clip/norm aux the accumulate
        step already releases — are read only on sampled ticks, so the
        host-device syncs stay at the sampled span boundaries."""
        obs = self.obs
        obs.inc("fit/steps")
        obs.inc("fit/examples", int(examples))
        obs.gauge("dp/eps", float(self.privacy_spent()[0]))
        obs.gauge("train/jit_entries", float(self._jit_entries()))
        if obs.sampled_now and acc_metrics:
            for key in ("clip_fraction", "mean_grad_norm", "max_grad_norm"):
                if key in acc_metrics:
                    # float() of a batch-aggregated scalar: the one
                    # device->host read, at the sampled boundary only
                    obs.gauge(f"dp/{key}", float(acc_metrics[key]))

    def evaluate(self, batch, mask=None) -> float:
        if mask is None:
            b0 = jax.tree.leaves(batch)[0]
            mask = jnp.ones(b0.shape[0], jnp.float32)
        self._configure_train()
        batch, mask = self.executor.place(batch, mask)
        return float(self._jitted("evaluate")(self.state.params, batch, mask))

    def fit(self, dataset=None, steps: Optional[int] = None, *, ckpt: Optional[str] = None,
            ckpt_every: int = 0, ckpt_keep: int = 3) -> dict:
        """Run the full loop: sampler (``TrainConfig.sampler``) ->
        BatchMemoryManager -> accumulate/update -> accountant
        (-> checkpoint).  Returns the same record the legacy
        ``launch.train.train`` driver produced.

        ``steps`` counts the optimizer steps THIS call takes; the sampler
        stream is indexed by the ABSOLUTE optimizer step, so a restored
        session continues the counter-based draws exactly where the
        uninterrupted run would be (never replaying draws the restored
        accountant already charged — the exactly-once-sampling half of the
        resume invariant; every REGISTERED sampler satisfies the
        ``at_step(k)``/``start_step`` contract, enforced at registration).

        Checkpoints are written asynchronously (device→host copy + npz write
        on a background thread): with ``ckpt_every=N`` a snapshot is enqueued
        every N optimizer steps without stalling the step loop (it blocks
        only if the previous write is still in flight); the final checkpoint
        is always taken and made durable before fit returns.  Each snapshot
        commits via one atomic manifest rename; ``ckpt_keep`` manifests are
        retained for corruption fallback (older ones are GC'd)."""
        tc = self.train_cfg
        steps = steps if steps is not None else tc.steps
        # one host sync BEFORE the loop: the restored/current optimizer step
        # anchors the sampler stream and the checkpoint numbering
        start = int(self.state.step)
        if tc.target_eps is not None and start + steps > tc.steps:
            resumed = f" from step {start}" if start else ""
            raise ValueError(
                f"fit(steps={steps}){resumed} exceeds the {tc.steps} steps "
                f"sigma was calibrated for (target_eps={tc.target_eps}); "
                f"rebuild the session with TrainConfig(steps="
                f"{start + steps}) so calibration matches the steps "
                f"actually taken, or pass fit(steps={tc.steps - start}) to "
                f"finish the calibrated run.")
        if dataset is None:
            from ..data.synthetic import dataset_for_config
            dataset = dataset_for_config(self.model_cfg, tc.n_data,
                                         tc.seq_len, seed=tc.seed)
        else:
            n = getattr(dataset, "n", None)
            if n is not None and n != tc.n_data:
                raise ValueError(
                    f"dataset has n={n} examples but TrainConfig.n_data="
                    f"{tc.n_data}; q, delta and sigma calibration all depend "
                    f"on the population size — rebuild the session with "
                    f"TrainConfig(n_data={n}).")
        self._configure_train()
        sampler = make_sampler(tc.sampler, n=tc.n_data, q=tc.q, seed=tc.seed,
                               steps=steps, start_step=start)
        # the memory manager places each physical batch through the executor
        # as it is produced (host->device/mesh transfer off the step path)
        bmm = BatchMemoryManager(dataset.fetch, tc.physical_batch,
                                 place=self.executor.place)

        history = []
        obs = self.obs
        t0 = time.time()
        examples = 0
        # in-loop checkpoints derive the absolute step count host-side from
        # `start` (no device sync on the step path)
        init_step = start
        last_async_at = done = 0
        try:
            for step_i, indices in enumerate(sampler):
                obs.tick()
                with obs.span("fit/accumulate") as sp:
                    acc_metrics = None
                    for pb in bmm.batches(indices):
                        # pb is already placed by the memory manager's
                        # executor hook; call the jitted fn directly rather
                        # than accumulate(), which would place a second time
                        self.state, acc_metrics = self._jitted("accumulate")(
                            self.state, pb.data, pb.mask)
                    sp.watch(self.state.grad_acc)
                examples += len(indices)  # == sum of masks, no d2h sync
                with obs.span("fit/update") as sp:
                    self.state = self._jitted("update")(self.state)
                    sp.watch(self.state.params)
                with obs.span("fit/account"):
                    self._account()      # host-side RDP composition
                # the window the chaos suite cares about most: the accountant
                # has charged this step but no snapshot records it yet — a
                # kill here must resume from the PREVIOUS durable snapshot
                # and re-take this step with the same draw + noise
                fault_point("fit/after_account_before_ckpt")
                if obs.enabled:
                    self._record_step_telemetry(acc_metrics, step_i + 1,
                                                len(indices))
                if ckpt and ckpt_every and (step_i + 1) % ckpt_every == 0:
                    # optimizer steps taken == step_i + 1 on this loop, known
                    # host-side — no device sync on the step path.  The call
                    # blocks only while a PREVIOUS write is still in flight;
                    # that stall is the step loop's hidden cost, so it is
                    # always timed (host clock, no device sync) and warned
                    # about when it exceeds one mean step time.
                    t0c = time.perf_counter()
                    self.checkpoint_async(ckpt, step=init_step + step_i + 1,
                                          keep=ckpt_keep)
                    wait_s = time.perf_counter() - t0c
                    obs.observe("fit/ckpt_wait", float(wait_s))
                    mean_step = (time.time() - t0) / (step_i + 1)
                    if wait_s > mean_step:
                        obs.inc("fit/ckpt_wait_exceeded")
                        warnings.warn(
                            f"async checkpoint wait ({wait_s:.3f}s) exceeded "
                            f"one mean step time ({mean_step:.3f}s): the "
                            f"writer cannot keep up with ckpt_every="
                            f"{ckpt_every} — raise the interval or use "
                            f"faster storage", RuntimeWarning, stacklevel=2)
                    last_async_at = step_i + 1
                if (step_i + 1) % tc.log_every == 0:
                    idx_eval = np.arange(min(tc.physical_batch, tc.n_data))
                    eb = dataset.fetch(idx_eval)
                    with obs.span("fit/eval"):
                        l = self.evaluate(eb,
                                          np.ones(len(idx_eval), np.float32))
                    eps = self.privacy_spent()[0]
                    rec = {"step": step_i + 1, "loss": round(l, 4),
                           "eps": round(eps, 4),
                           "logical_batch": len(indices),
                           "throughput": round(examples / (time.time() - t0),
                                               1)}
                    history.append(rec)
                if (obs.snapshot_every
                        and (step_i + 1) % obs.snapshot_every == 0):
                    print(obs.snapshot(), file=sys.stderr)
                done = step_i + 1
                fault_point("fit/step_end")     # armed with at=N: "kill at
                #                                 step N of this fit call"
        except BaseException:
            # the loop died mid-flight: make the last enqueued snapshot
            # durable before propagating, so a crash never loses the
            # checkpoint that was already on its way to disk.  Flush
            # failures are swallowed here — the loop's exception is the one
            # the caller must see.
            if ckpt:
                try:
                    self.checkpoint_wait()
                except Exception:
                    pass
            raise
        if ckpt:
            if last_async_at and last_async_at == done:
                # the final state is already enqueued — just make it durable
                # instead of re-snapshotting and rewriting identical files
                self.checkpoint_wait()
            else:
                self.checkpoint(ckpt)
        return {"history": history, "sigma": self.dp.noise_multiplier,
                "final_eps": self.privacy_spent()[0],
                "examples_per_s": examples / (time.time() - t0)}

    def privacy_spent(self) -> tuple:
        """(eps, delta) actually spent so far, from the accountant."""
        if not self.dp.private or not self.accountant.history:
            return 0.0, self.accountant.delta
        return self.accountant.spent()

    def _ckpt_meta(self) -> dict:
        eps, delta = self.privacy_spent()
        return {"arch": getattr(self.model_cfg, "name", "?"),
                "engine": self.dp.engine, "eps": eps, "delta": delta,
                "sampler": self.train_cfg.sampler,
                # which Philox key layout drew the charged steps — restore()
                # warns when resuming across a stream-version break
                "sampler_stream_version": SAMPLER_STREAM_VERSION,
                # full (q, sigma, steps, sampler) history: restore() replays
                # the exact composition instead of assuming constant values
                "accountant": self.accountant.state_dict()}

    def checkpoint_async(self, path: str, *, step: Optional[int] = None,
                         keep: Optional[int] = None) -> None:
        """Enqueue a checkpoint on the background writer and return — the
        step loop keeps running while d2h + npz write happen off-thread.
        Blocks only if a previous write is still in flight.  Pass ``step``
        when the caller knows it host-side (fit's loop does): reading
        ``state.step`` would force a host-device sync on the step path.
        The snapshot carries the train-state RNG key so a restore continues
        the noise stream bit-exactly."""
        from ..checkpoint import AsyncCheckpointer
        if self._ckpt_writer is None:
            # resilience counters (ckpt/saves|retries|failures) flow through
            # the session's registry
            self._ckpt_writer = AsyncCheckpointer(obs=self.obs)
        if keep is not None:
            self._ckpt_writer.keep = keep
        if step is None:
            step = int(self.state.step)
        self._ckpt_writer.save(path, self.state.params, self.state.opt_state,
                               step, self._ckpt_meta(),
                               extra={"rng": self.state.rng})

    def checkpoint_wait(self) -> None:
        """Make the last enqueued checkpoint durable (no-op when idle)."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.wait()

    def checkpoint(self, path: str) -> None:
        """Synchronous checkpoint: enqueue + wait until durable."""
        self.checkpoint_async(path)
        self.checkpoint_wait()

    # -- reporting ----------------------------------------------------------

    def describe(self) -> dict:
        """Engine, σ, q, δ and the expected ε trajectory over the configured
        number of steps — the benchmark/report header."""
        tc, dp = self.train_cfg, self.dp
        traj = []
        if dp.private and dp.noise_multiplier > 0:
            per_step = rdp_mod.compose_for(tc.sampler, self._sampler_q,
                                           dp.noise_multiplier, 1)
            acc = np.zeros_like(per_step)
            for _ in range(tc.steps):
                acc = acc + per_step
                traj.append(round(rdp_mod.rdp_to_eps(
                    acc, tc.resolved_delta), 4))
        return {
            "arch": getattr(self.model_cfg, "name", "?"),
            "engine": dp.engine,
            "sigma": dp.noise_multiplier,
            "clip_norm": dp.clip_norm,
            "sampler": tc.sampler,
            "q": self._sampler_q,
            "delta": tc.resolved_delta,
            "expected_batch_size": dp.expected_batch_size,
            "physical_batch": tc.physical_batch,
            "microbatches": dp.microbatches,
            "steps": tc.steps,
            "optimizer": tc.optimizer,
            "expected_eps_trajectory": traj,
            "eps_spent": self.privacy_spent()[0],
            "optimizer_steps_taken": int(self.state.step),
            "launch": self.executor.describe(),
        }

    # -- serving ------------------------------------------------------------

    def serve_engine(self, *, max_slots: int = 4, max_len: int = 64,
                     extras: Optional[dict] = None, prefill_chunk: int = 1,
                     token_budget: Optional[int] = None,
                     prefix_sharing: bool = True, obs=None):
        """A :class:`~repro.serve.ServeEngine` over the session's CURRENT
        parameters and executor, cached per (max_slots, max_len,
        prefill_chunk, token_budget, prefix_sharing) so repeated
        ``generate()`` calls reuse the compiled decode step.  On reuse the
        engine is refreshed — post-``fit()`` params AND the cache-pool
        template they imply (cross-KV caches are precomputed from params/
        extras, not just zeros)."""
        from ..serve import ServeEngine
        key = ("serve", max_slots, max_len, prefill_chunk, token_budget,
               prefix_sharing)
        engine = self._jit_cache.get(key)
        if engine is None:
            engine = ServeEngine.from_session(
                self, max_slots=max_slots, max_len=max_len, extras=extras,
                prefill_chunk=prefill_chunk, token_budget=token_budget,
                prefix_sharing=prefix_sharing, obs=obs)
            self._jit_cache[key] = engine
        else:
            engine.refresh(self.state.params, extras=extras)
            if obs is not None:
                engine.obs = as_registry(obs)
        return engine

    def generate(self, *, batch: int = 4, prompt_len: int = 8,
                 new_tokens: int = 8, max_len: int = 64, greedy: bool = True,
                 temperature: float = 1.0, top_k: int = 0) -> dict:
        """Autoregressive generation with the session's current parameters
        (e.g. after fit() or restore()) — a thin single-batch wrapper over
        :class:`~repro.serve.ServeEngine`: ``batch`` synthetic requests are
        submitted together and drained through the continuous-batching
        scheduler.  ``greedy=False`` samples at ``temperature`` with
        optional ``top_k`` truncation, each request on its own PRNG stream
        (seeded from ``TrainConfig.seed`` + request index)."""
        from ..serve import Request, SamplingParams
        cfg, tc = self.model_cfg, self.train_cfg
        if prompt_len + new_tokens > max_len:
            raise ValueError(
                f"prompt_len({prompt_len}) + new_tokens({new_tokens}) "
                f"exceeds max_len={max_len}: the cache would fill before "
                f"generation completes (raise max_len)")
        rng = jax.random.PRNGKey(tc.seed + 1)
        prompt = np.asarray(jax.random.randint(
            rng, (batch, prompt_len), 0, cfg.vocab))

        # synthetic frontends are cached per batch size: the SAME arrays are
        # handed to serve_engine each call, so engine.refresh() recognises
        # them and skips rebuilding the cache-pool template (whisper's
        # init_cache runs a full encoder forward)
        ekey = ("gen_extras", batch)
        extras = self._jit_cache.get(ekey)
        if extras is None:
            extras = {}
            if cfg.family == "vlm":
                extras["frontend"] = jax.random.normal(
                    rng, (batch, cfg.n_image_tokens, cfg.frontend_dim)) * 0.1
            if cfg.family == "audio":
                extras["frontend"] = jax.random.normal(
                    rng, (batch, cfg.n_audio_frames, cfg.d_model)) * 0.1
            self._jit_cache[ekey] = extras

        engine = self.serve_engine(max_slots=batch, max_len=max_len,
                                   extras=extras or None)
        temp = 0.0 if greedy else temperature
        reqs = [Request(prompt=prompt[i].tolist(), max_new_tokens=new_tokens,
                        sampling=SamplingParams(temperature=temp, top_k=top_k,
                                                seed=tc.seed + 1 + i))
                for i in range(batch)]
        t0 = time.time()
        out = engine.run(reqs)
        dt = max(time.time() - t0, 1e-9)
        by_rid = {r["rid"]: r["generated"] for r in out["results"]}
        first = min(by_rid)
        return {"generated": [by_rid[first + i] for i in range(batch)],
                "tokens_per_s": round(batch * (prompt_len + new_tokens) / dt, 1),
                "iterations": out["iterations"],
                "occupancy": out["occupancy"]}
