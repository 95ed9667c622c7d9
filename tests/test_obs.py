"""The observability subsystem's contract.

Three properties matter more than any individual metric:

  1. **off is free** — the default mode adds ZERO device sync points
     (pinned structurally with a raising ``sync=`` injection AND end-to-end
     by monkeypatching ``jax.block_until_ready`` under a full ``fit()``),
  2. **sampled is phase-accurate** — spans sync their watched device value
     at the boundary, only on sampled ticks,
  3. **telemetry is DP-safe** — the L005 lint rule rejects any tap inside
     the DP boundary whose value is not a literal or aggregated/coerced
     (mutation-fixture style, like tests/test_analysis.py).

Plus the plumbing: deterministic-clock span nesting, histogram percentile
math, JSONL schema round-trip, fit()/ServeEngine emission, the ckpt-wait
counter/warning, and the ``--profile`` -> ``--trace-shape`` CLI rename.
"""
import argparse
import sys
import textwrap
import time
import warnings

import numpy as np
import pytest

import jax

from repro.analysis.lint import lint_paths
from repro.core import DPConfig
from repro.core.session import PrivacySession, TrainConfig
from repro.obs import (Histogram, JsonlExporter, MetricsRegistry, ObsConfig,
                       SCHEMA_VERSION, add_cli_args, config_from_args,
                       read_jsonl)
from repro.serve import Request, ServeEngine


class FakeClock:
    """Deterministic clock: every read advances 1s."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


class ListExporter:
    def __init__(self):
        self.records = []

    def emit(self, record):
        self.records.append(record)

    def close(self):
        pass


def _dp_session(obs=None, **tc_kw):
    dp = DPConfig(clip_norm=0.1, noise_multiplier=0.7, engine="masked_pe")
    tc = TrainConfig(steps=2, n_data=16, q=0.25, seq_len=8, physical_batch=4,
                     seed=0, lr=0.1, optimizer="sgd", momentum=0.0, **tc_kw)
    return PrivacySession.from_config("qwen2-0.5b", dp, tc, obs=obs)


@pytest.fixture(scope="module")
def qwen():
    return PrivacySession.from_config(
        "qwen2-0.5b", DPConfig(engine="nonprivate"),
        TrainConfig(seed=0, smoke=True))


# -- metrics core -----------------------------------------------------------

def test_span_nesting_with_injected_clock():
    """Nested spans time correctly off a deterministic clock and record
    their parent; exported records carry name/parent/tick/duration."""
    exp = ListExporter()
    reg = MetricsRegistry("events", clock=FakeClock(), exporter=exp)
    reg.tick()
    with reg.span("outer"):
        with reg.span("inner"):
            pass
    # inner: enter t=2, exit t=3; outer: enter t=1, exit t=4
    assert reg.hists["inner"].total == pytest.approx(1.0)
    assert reg.hists["outer"].total == pytest.approx(3.0)
    spans = [r for r in exp.records if r["kind"] == "span"]
    assert [s["name"] for s in spans] == ["inner", "outer"]  # exit order
    assert spans[0]["parent"] == "outer"
    assert spans[1]["parent"] is None
    assert all(s["tick"] == 1 and not s["synced"] for s in spans)


def test_histogram_percentile_math():
    h = Histogram()
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100 and h.mean == pytest.approx(50.5)
    assert (h.vmin, h.vmax) == (1.0, 100.0)
    # nearest-rank: ceil(q*n)-1
    assert h.percentile(0.5) == 50.0
    assert h.percentile(0.95) == 95.0
    assert h.percentile(0.0) == 1.0 and h.percentile(1.0) == 100.0
    # the ring is bounded but count/total stay exact
    small = Histogram(cap=4)
    for v in (1.0, 2.0, 3.0, 4.0, 100.0):
        small.observe(v)
    assert small.count == 5 and small.total == pytest.approx(110.0)
    assert small.percentile(1.0) == 100.0      # over the retained ring


def test_jsonl_schema_roundtrip(tmp_path):
    p = str(tmp_path / "log.jsonl")
    exp = JsonlExporter(p)
    reg = MetricsRegistry("events", clock=FakeClock(), exporter=exp)
    reg.tick()
    with reg.span("phase"):
        pass
    reg.gauge("g", 2.5)
    reg.event("request", rid=7, ttft_s=0.01)
    reg.close()                                 # dump_stats + close
    body = read_jsonl(p)
    assert [r["kind"] for r in body] == ["span", "gauge", "event", "stats"]
    assert read_jsonl(p, kind="gauge") == [
        {"kind": "gauge", "name": "g", "tick": 1, "value": 2.5}]
    assert read_jsonl(p, kind="event")[0]["rid"] == 7
    stats = read_jsonl(p, kind="stats")[0]
    assert stats["gauges"]["g"] == 2.5 and "phase" in stats["spans"]
    # a future schema version is refused, not silently misread
    lines = open(p).read().splitlines()
    bad = tmp_path / "bad.jsonl"
    bad.write_text(lines[0].replace(f'"version": {SCHEMA_VERSION}',
                                    f'"version": {SCHEMA_VERSION + 1}')
                   + "\n" + "\n".join(lines[1:]))
    with pytest.raises(ValueError, match="schema version"):
        read_jsonl(str(bad))
    notlog = tmp_path / "x.jsonl"
    notlog.write_text('{"kind": "span"}\n')
    with pytest.raises(ValueError, match="schema header"):
        read_jsonl(str(notlog))


def test_off_mode_zero_syncs_sampled_mode_syncs():
    """The structural no-sync guarantee: a raising sync injection proves
    off mode (and non-sampled ticks) never touch the watched value."""
    def boom(x):
        raise AssertionError("sync point in off mode")

    off = MetricsRegistry("off", sync=boom)
    off.tick()
    with off.span("phase") as sp:
        sp.watch(object())
    off.inc("c")
    off.gauge("g", 1.0)
    assert not off.counters and not off.gauges and not off.hists

    calls = []
    reg = MetricsRegistry("sampled", sample_every=2, sync=calls.append,
                          clock=FakeClock())
    for _ in range(4):
        reg.tick()
        with reg.span("phase") as sp:
            sp.watch("v")
    # ticks 2 and 4 are sampled: exactly those sync and are timed
    assert calls == ["v", "v"]
    assert reg.hists["phase"].count == 2


def test_fit_off_mode_adds_no_block_until_ready(monkeypatch):
    """End-to-end: a default (uninstrumented) fit() never calls
    jax.block_until_ready — observability costs nothing when off."""
    def boom(x):
        raise AssertionError("fit() hit block_until_ready in off mode")

    monkeypatch.setattr(jax, "block_until_ready", boom)
    out = _dp_session().fit()
    assert len(out["history"]) == 2


# -- fit() emission ---------------------------------------------------------

def test_fit_emits_spans_and_dp_gauges(tmp_path):
    p = str(tmp_path / "train.jsonl")
    session = _dp_session(obs=ObsConfig(mode="sampled", jsonl=p))
    session.fit()
    session.obs.close()
    span_names = {r["name"] for r in read_jsonl(p, kind="span")}
    assert {"fit/accumulate", "fit/update", "fit/account",
            "fit/eval"} <= span_names
    # sampled spans covered their watched device output
    assert all(r["synced"] for r in read_jsonl(p, kind="span")
               if r["name"] in ("fit/accumulate", "fit/update"))
    gauges = {r["name"] for r in read_jsonl(p, kind="gauge")}
    assert {"dp/eps", "train/jit_entries", "dp/clip_fraction",
            "dp/mean_grad_norm", "dp/max_grad_norm"} <= gauges
    # the eps trajectory is monotone and matches the accountant's total
    eps = [r["value"] for r in read_jsonl(p, kind="gauge")
           if r["name"] == "dp/eps"]
    assert len(eps) == 2 and eps == sorted(eps)
    assert eps[-1] == pytest.approx(session.privacy_spent()[0])
    stats = read_jsonl(p, kind="stats")[0]
    assert stats["counters"]["fit/steps"] == 2
    assert 0.0 <= stats["gauges"]["dp/clip_fraction"] <= 1.0


def test_fit_surfaces_ckpt_wait(tmp_path, monkeypatch):
    """checkpoint_async stalls are timed, counted, and warned about when
    they exceed one mean step time."""
    session = _dp_session(obs=ObsConfig(mode="events"))
    # the registry captured the real perf_counter at construction; the fit
    # loop's ckpt timing looks it up per call — fake a 100s wait there
    fake_t = [0.0]

    def fake_perf_counter():
        fake_t[0] += 100.0
        return fake_t[0]

    monkeypatch.setattr(time, "perf_counter", fake_perf_counter)
    with pytest.warns(RuntimeWarning, match="checkpoint wait"):
        session.fit(ckpt=str(tmp_path / "ck"), ckpt_every=1)
    assert session.obs.hists["fit/ckpt_wait"].count == 2
    assert session.obs.counters["fit/ckpt_wait_exceeded"] == 2


# -- serving emission -------------------------------------------------------

def test_serve_phase_breakdown_and_request_events(tmp_path, qwen):
    p = str(tmp_path / "serve.jsonl")
    obs = ObsConfig(mode="sampled", jsonl=p).build()
    engine = ServeEngine.from_session(qwen, max_slots=2, max_len=32, obs=obs)
    out = engine.run([Request(prompt=[1, 2, 3], max_new_tokens=4),
                      Request(prompt=[4, 5], max_new_tokens=3)])
    pb = out["phase_breakdown"]
    assert {"admit", "decode", "sample", "host_sync"} <= set(pb)
    for rec in pb.values():
        assert rec["calls"] >= 1
        # both fields are independently rounded in the report
        assert rec["mean_ms"] == pytest.approx(
            rec["total_ms"] / rec["calls"], abs=1e-3)
    assert obs.counters["serve/requests_finished"] == 2
    assert obs.hists["serve/ttft"].count == 2
    obs.close()
    events = read_jsonl(p, kind="event")
    assert {e["rid"] for e in events} == {0, 1}
    for e in events:
        assert e["name"] == "request" and e["finish_reason"] == "length"
        assert e["ttft_s"] is not None and e["queue_s"] is not None
    # a second run reports ITS phases, not cumulative totals
    out2 = engine.run([Request(prompt=[6, 7], max_new_tokens=2)])
    assert out2["phase_breakdown"]["decode"]["calls"] <= \
        pb["decode"]["calls"] + 2


def test_engine_inherits_session_registry(qwen):
    engine = ServeEngine.from_session(qwen, max_slots=1, max_len=32)
    assert engine.obs is qwen.obs            # train + serve: one registry
    mine = MetricsRegistry("events")
    engine2 = ServeEngine.from_session(qwen, max_slots=1, max_len=32,
                                       obs=mine)
    assert engine2.obs is mine


# -- L005: DP-boundary tap lint (mutation fixtures) -------------------------

def test_l005_flags_unreleased_tap_inside_boundary(tmp_path):
    core = tmp_path / "core"
    core.mkdir()
    (core / "bad.py").write_text(textwrap.dedent("""
        def accumulate(obs, per_example_norms, aux):
            obs.gauge("dp/norms", per_example_norms)
            obs.observe("dp/one", aux["per_example_norms"][0])
            self.metrics.event("step", norms=per_example_norms)
    """))
    findings = lint_paths([str(tmp_path)], semantic=False)
    l5 = [f for f in findings if f.code == "L005"]
    assert len(l5) == 3
    assert all("per-example" in f.message for f in l5)


def test_l005_accepts_released_and_aggregated_taps(tmp_path):
    core = tmp_path / "core"
    core.mkdir()
    (core / "ok.py").write_text(textwrap.dedent("""
        def accumulate(obs, norms, mask, eps, key):
            obs.gauge("dp/mean_norm", float((norms * mask).mean()))
            obs.gauge(f"dp/{key}", float(eps))
            obs.inc("fit/steps")
            obs.inc("fit/examples", int(mask.sum()))
            obs.observe("dp/agg", norms.max())
            obs.gauge("dp/known", eps)  # lint: dp-released
            x = jnp.zeros(4).at[0].set(norms)     # not a tap: jax .set
    """))
    assert [f for f in lint_paths([str(tmp_path)], semantic=False)
            if f.code == "L005"] == []


def test_l005_scoped_to_dp_boundary(tmp_path):
    serve = tmp_path / "serve"
    serve.mkdir()
    (serve / "sched.py").write_text(
        "def f(obs, logits):\n    obs.gauge('serve/x', logits)\n")
    assert [f for f in lint_paths([str(tmp_path)], semantic=False)
            if f.code == "L005"] == []


def test_l005_src_tree_is_clean():
    import os
    import repro.obs
    src = os.path.dirname(os.path.dirname(repro.obs.__file__))
    assert [f for f in lint_paths([src], semantic=False)
            if f.code == "L005"] == []


# -- CLI --------------------------------------------------------------------

def test_obs_cli_flags_roundtrip(tmp_path):
    ap = argparse.ArgumentParser()
    add_cli_args(ap)
    args = ap.parse_args(["--metrics", "sampled", "--sample-every", "3",
                          "--metrics-jsonl", str(tmp_path / "m.jsonl"),
                          "--metrics-every", "10"])
    reg = config_from_args(args).build()
    assert (reg.mode, reg.sample_every, reg.snapshot_every) == ("sampled",
                                                                3, 10)
    assert reg.exporter is not None
    reg.close()
    # --profile-dir alone bumps off -> events so spans exist to annotate
    reg2 = ObsConfig(profile_dir=str(tmp_path / "prof")).build()
    assert reg2.mode == "events" and reg2.annotate


@pytest.mark.parametrize("which", ["start", "stop"])
def test_profile_failure_raises(which, monkeypatch, tmp_path):
    """A run asked for --profile-dir must not exit 0 without a trace: a
    profiler that fails to start or stop raises instead of warning."""
    from repro.obs import start_profile, stop_profile

    def broken(*a, **k):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, f"{which}_trace", broken)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        if which == "start":
            start_profile(str(tmp_path / "prof"))
        else:
            stop_profile()


def test_serve_cli_profile_renamed_to_trace_shape(monkeypatch, capsys):
    from repro.launch import serve as serve_cli
    seen = {}

    def fake_replay(arch, **kw):
        seen.update(kw)
        return {"ok": True}

    monkeypatch.setattr(serve_cli, "replay", fake_replay)
    # the CLI turns on the persistent compile cache; a test leaves it off
    monkeypatch.setattr(serve_cli, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv",
                        ["serve", "--requests", "2", "--profile", "bimodal"])
    with pytest.warns(DeprecationWarning, match="--trace-shape"):
        serve_cli.main()
    assert seen["trace_shape"] == "bimodal"
    seen.clear()
    monkeypatch.setattr(sys, "argv", ["serve", "--requests", "2",
                                      "--trace-shape", "bimodal"])
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        serve_cli.main()
    assert seen["trace_shape"] == "bimodal"
    capsys.readouterr()
