"""Unified telemetry: phase-accurate spans, DP-safe metrics, trace export.

The paper is a *measurement* paper — it attributes the cost of correct
Poisson-subsampled DP-SGD phase by phase.  This package makes that
attribution a production property instead of a benchmark-script one:

* :mod:`.metrics` — the core: a process-local :class:`MetricsRegistry` of
  counters/gauges/histograms plus ``span(name)`` phase timers that are
  async-dispatch-aware (``block_until_ready`` only at span boundaries in
  ``sampled`` mode; the default ``off`` mode is a strict no-op with ZERO
  added sync points).  Deterministic injectable clock for tests.
* :mod:`.export` — a schema-versioned JSONL event log (spans, gauges,
  request lifecycle events, an aggregate ``stats`` flush), human-readable
  snapshots, and ``jax.profiler`` trace capture with spans wrapped in
  ``TraceAnnotation``.
* :mod:`.phases` — the named scopes of the DP phases inside the jitted
  step (``grad/per_example``, ``grad/norm_pass``, ``grad/reweighted``,
  ``grad/batch``, ``grad/accumulate``): compile-time ``op_name``
  metadata that the device trace attributes time to.

Instrumentation taps live in :meth:`repro.core.session.PrivacySession.fit`
(accumulate / update / account / ckpt-wait spans, the input path's
``fit/sample`` / ``fit/fetch`` / ``fit/place`` host spans — the last two in
:class:`repro.data.loader.BatchMemoryManager` — ε-trajectory and
clip-fraction gauges read ONLY from already-aggregated step aux), in the
clipping engines and step builders of :mod:`repro.core` (the phase scopes)
and in
:class:`repro.serve.Scheduler` (admit / prefill / decode / sample /
host-sync spans, per-request queue/TTFT/TPOT/prefix-hit events) — so
``engine.run``'s report and ``bench_serving`` read the same numbers from
one source.  The L005 lint rule (:mod:`repro.analysis.lint`) keeps every
tap inside the DP boundary reading only released or batch-aggregated
values — observability can never become a per-example side channel.
"""
from __future__ import annotations

from .export import (JsonlExporter, read_jsonl, start_profile,  # noqa: F401
                     stop_profile)
from .metrics import (MODES, NULL_REGISTRY, SCHEMA_VERSION,  # noqa: F401
                      Histogram, MetricsRegistry, ObsConfig, add_cli_args,
                      as_registry, config_from_args)
from .phases import PHASES, phase  # noqa: F401

__all__ = [
    "MODES", "SCHEMA_VERSION", "Histogram", "MetricsRegistry", "ObsConfig",
    "NULL_REGISTRY", "as_registry", "add_cli_args", "config_from_args",
    "JsonlExporter", "read_jsonl", "start_profile", "stop_profile",
    "PHASES", "phase",
]
