"""The split of a traced window by the program's own phases: device time
per DP phase, and idle device time per host span.

* :func:`op_phases` reads a compiled program's HLO text: each instruction
  whose ``op_name`` metadata holds a phase of the program's vocabulary
  (the named scopes of ``repro.obs.phases``) maps to the innermost one.
* :func:`phase_table` sums the reduced trace's device operations
  (``trace.reduce``'s ``ops``) by those phases.
* :func:`idle_under` gives, per host span name, the idle device time
  during which a span of that name was open at any depth; the reduced
  trace's ``gaps`` name each gap by its innermost span only, so a pool's
  ``input/fetch`` hides the program's ``fit/fetch`` around it.

A program without the scopes or spans (an older checkout) gives empty
tables, and the metrics that read them are left out.
"""
from __future__ import annotations

import array
import importlib.util
import os
import re

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# the program's phase vocabulary (repro.obs.phases.PHASES), kept here so
# that the benchmark reads a checkout that lacks it
PHASES = ("grad/per_example", "grad/norm_pass", "grad/reweighted",
          "grad/batch", "grad/accumulate")
# the phases that compute a gradient; the rest moves it to the accumulator
GRAD_PHASES = PHASES[:4]
FOLD_PHASE = "grad/accumulate"
# the host spans of the program's input path
INPUT_SPANS = ("fit/sample", "fit/fetch", "fit/place")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# a phase as whole path components: after the start, "/" or a transform's
# "(", and before the end, "/" or ")"
_PHASE = re.compile(r"(?:^|(?<=[/(]))(" + "|".join(
    re.escape(p) for p in PHASES) + r")(?=$|[/)])")


def _trace():
    """``trace.py`` of this directory, by file path (the standard library
    has a module of that name)."""
    path = os.path.join(HERE, "trace.py")
    spec = importlib.util.spec_from_file_location("bench_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def innermost_phase(op_name: str):
    """The last phase of the vocabulary on ``op_name``'s path, or None."""
    found = _PHASE.findall(op_name)
    return found[-1] if found else None


def op_phases(text: str) -> dict:
    """Instruction name (as the device trace names the op) -> the
    innermost phase on its ``op_name``; instructions with none are left
    out."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name = _OP_NAME.search(line)
        ph = innermost_phase(name.group(1)) if name else None
        if ph is not None:
            out[m.group(1)] = ph
    return out


def phase_table(red: dict, phases: dict) -> dict:
    """``{program: {phase: {"s", "n"}}}`` of device time, from the reduced
    trace's ops and ``phases`` (program -> :func:`op_phases` of its HLO).
    Container ops (``while``, ``conditional``, ``call``) are left out:
    their time overlaps their bodies'."""
    containers = _trace().CONTAINERS
    out = {}
    for (prog, op), rec in red["ops"].items():
        ph = phases.get(prog, {}).get(op)
        if ph is None or op.startswith(containers):
            continue
        r = out.setdefault(prog, {}).setdefault(ph, {"s": 0.0, "n": 0})
        r["s"] += rec["s"]
        r["n"] += rec["n"]
    return out


def _measure_before(begin, end, t):
    """For sorted disjoint intervals ``[begin, end)``: the length of their
    union that lies before each time in ``t``."""
    cum = np.concatenate([[0.0], np.cumsum(end - begin)])
    i = np.searchsorted(begin, t, side="right")
    j = np.maximum(i - 1, 0)
    inside = np.where(i > 0, np.clip(t - begin[j], 0.0, end[j] - begin[j]),
                      0.0)
    return cum[j] * (i > 0) + inside


def idle_under(profile, device_ids) -> dict:
    """Host span name -> idle device seconds inside the measured window
    during which a span of that name was open, at any depth, averaged over
    the chips ``device_ids``."""
    tr = _trace()
    planes = list(profile.planes)
    host = tr.HostSpans(planes)
    if host.win is None:
        raise ValueError(f"no {tr.WINDOW_SPAN!r} span on the host")
    lo, hi = host.win
    chips = {int(m.group(1)): p for p in planes
             if (m := re.fullmatch(r"/device:TPU:(\d+)", p.name))}
    spans = {}
    for s, e, name in host.spans:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            b, f = spans.setdefault(name, ([], []))
            b.append(s)
            f.append(e)
    spans = {name: tr.union(np.array(b, float), np.array(e, float))
             for name, (b, e) in spans.items()}
    n = len(device_ids)
    out = dict.fromkeys(spans, 0.0)
    for d in device_ids:
        lines = {ln.name: ln for ln in chips[d].lines}
        begin, end = array.array("d"), array.array("d")
        for ev in lines[tr.OPS_LINE].events:
            s, e = ev.start_ns, ev.end_ns
            if e > lo and s < hi:
                begin.append(max(s, lo))
                end.append(min(e, hi))
        busy = tr.union(np.frombuffer(begin), np.frombuffer(end))
        edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        for name, u in spans.items():
            idle = (_measure_before(gaps[:, 0], gaps[:, 1], u[:, 1])
                    - _measure_before(gaps[:, 0], gaps[:, 1], u[:, 0]))
            out[name] += float(idle.sum()) / 1e9 / n
    return out
