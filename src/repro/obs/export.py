"""Exporters: JSONL event log, snapshot helpers, jax.profiler traces.

JSONL schema (version :data:`~repro.obs.metrics.SCHEMA_VERSION`)
----------------------------------------------------------------

The first line of every log is a header record::

    {"kind": "schema", "version": 1, "source": "repro.obs"}

Every subsequent line is one record with a ``kind``:

* ``span``   — one timed phase: ``name``, ``parent`` (enclosing span or
  null), ``tick`` (step / scheduler-iteration counter), ``t0`` (registry
  clock at entry), ``dur_s``, ``synced`` (True when the duration covered a
  ``block_until_ready`` on the phase's device output — sampled mode).
* ``gauge``  — ``name``, ``tick``, ``value``.
* ``event``  — structured one-offs (request lifecycle): ``name``, ``tick``
  plus free-form fields (``rid``, ``queue_s``, ``ttft_s``, ``tpot_s``, ...).
* ``stats``  — the aggregate flush :meth:`MetricsRegistry.dump_stats`
  writes: ``counters``, ``gauges`` and per-span count/total/mean/p50/p95.

:func:`read_jsonl` is the consuming side (benchmarks, tests): it validates
the header version and returns the records.

Profiler traces
---------------

:func:`start_profile` / :func:`stop_profile` wrap ``jax.profiler``'s trace
capture; while a trace is live, every registry built with
``ObsConfig(profile_dir=...)`` wraps its spans in
``jax.profiler.TraceAnnotation`` so the phase names land inside the
TensorBoard / perfetto timeline next to the XLA ops they dispatched.
"""
from __future__ import annotations

import json
import os
from typing import List, Optional

from .metrics import SCHEMA_VERSION


class JsonlExporter:
    """Append-only JSONL event log with a schema-version header."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._f = open(path, "w")
        self.emit({"kind": "schema", "version": SCHEMA_VERSION,
                   "source": "repro.obs"})

    def emit(self, record: dict) -> None:
        if self._f is None:
            return
        self._f.write(json.dumps(record, default=float) + "\n")
        self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


def read_jsonl(path: str, kind: Optional[str] = None) -> List[dict]:
    """Read an event log back, validating the schema header.  ``kind``
    filters to one record kind (the header is always dropped)."""
    with open(path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    if not records or records[0].get("kind") != "schema":
        raise ValueError(f"{path}: not a repro.obs event log "
                         f"(missing schema header)")
    version = records[0].get("version")
    if version != SCHEMA_VERSION:
        raise ValueError(f"{path}: schema version {version} != supported "
                         f"{SCHEMA_VERSION}")
    body = records[1:]
    if kind is not None:
        body = [r for r in body if r.get("kind") == kind]
    return body


def start_profile(profile_dir: str) -> None:
    """Start a jax.profiler trace into ``profile_dir`` (TensorBoard /
    perfetto format).  A trace that cannot start raises: a run asked for a
    profile must not exit 0 without one."""
    import jax
    jax.profiler.start_trace(profile_dir)


def stop_profile() -> None:
    """Stop the live trace and write it out; raises when that fails."""
    import jax
    jax.profiler.stop_trace()
