"""The reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Per chip the trace has a plane ``/device:TPU:<n>`` with a line of program
runs (``XLA Modules``, one event per execution of a jitted program) and a
line of device operations (``XLA Ops``).  The host's plane (``/host:CPU``)
holds the host's spans, among them the ``TraceAnnotation`` s that the
program's spans and the benchmark's own become.  Every time is on one
clock, in nanoseconds.

:func:`reduce` returns, averaged over the chips the cell uses:

* ``window_s``: the measured window (the benchmark's ``bench/window``
  span; without it, first to last device operation);
* ``busy_s``: the union of the device operations' intervals inside it;
* ``programs``: per program (``accumulate``, ``update``, ...) the device
  time of its runs and how many there were;
* ``ops``: per (program, operation name) device time and count;
* ``collectives``: device time and count of the collective operations
  (all-reduce and kin) inside the accumulate program;
* ``breakdown``: the ten operations that took most time, and the ten
  longest kinds of idle gap, each named by the innermost host span open at
  the gap's middle.
"""
from __future__ import annotations

import array
import bisect
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LONG_NS = 1e9
PROGRAMS = ("accumulate", "update", "evaluate", "step")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")


def load(trace_dir: str):
    """The trace the profiler wrote under ``trace_dir``."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {files}")
    return jax.profiler.ProfileData.from_file(files[0])


def program_of(module_name: str) -> str:
    """``jit_accumulate(123)`` -> ``accumulate``; other names as they
    are."""
    for p in PROGRAMS:
        if re.search(rf"(^|[_.]){p}($|[(_.\s])", module_name):
            return p
    return module_name


CONTAINERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


class HostSpans:
    """The spans of the host thread that ran the window (the line holding
    ``bench/window``), for naming what the program was doing at a moment;
    spans of other threads (the runtime's transfer workers) are left out."""

    def __init__(self, planes):
        self.spans, self.win = [], None
        for plane in planes:
            if not plane.name.startswith("/host"):
                continue
            for line in plane.lines:
                if self.win is None and any(e.name == WINDOW_SPAN
                                            for e in line.events):
                    spans = [(e.start_ns, e.end_ns, e.name)
                             for e in line.events]
                    self.win = next((s, e) for s, e, n in spans
                                    if n == WINDOW_SPAN)
                    self.spans = sorted(x for x in spans if x[1] > x[0])
        # spans up to a second long are searched by start; the few longer
        # ones (the window, a whole fit call) one by one
        self.short = [x for x in self.spans if x[1] - x[0] <= LONG_NS]
        self.long = [x for x in self.spans if x[1] - x[0] > LONG_NS]
        self.starts = [s for s, _, _ in self.short]

    def at(self, t: float) -> str:
        """The innermost span open at ``t`` (``idle`` when none is)."""
        best, best_len = "idle", None
        i = bisect.bisect_right(self.starts, t)
        lo = bisect.bisect_left(self.starts, t - LONG_NS)
        for s, e, n in self.short[lo:i] + self.long:
            if s <= t < e and (best_len is None or e - s < best_len):
                best, best_len = n, e - s
        return best


def _device(plane, lo, hi, host, n, acc):
    """Adds one chip's share (1/n) to the sums in ``acc``."""
    lines = {ln.name: ln for ln in plane.lines}
    mods = sorted((e.start_ns, e.end_ns, program_of(e.name))
                  for e in lines[MODULES_LINE].events) \
        if MODULES_LINE in lines else []
    for s, e, prog in mods:
        if s < hi and e > lo:
            rec = acc["programs"].setdefault(prog, {"s": 0.0, "n": 0})
            rec["s"] += (e - s) / 1e9 / n
            rec["n"] += 1 / n
    starts = [s for s, _, _ in mods]
    begin, end = array.array("d"), array.array("d")
    for ev in lines[OPS_LINE].events:
        s, e = ev.start_ns, ev.end_ns
        if e <= lo or s >= hi:
            continue
        name = short_name(ev.name)
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and mods[i][1] >= e else "?"
        rec = acc["ops"].setdefault((prog, name), {"s": 0.0, "n": 0})
        rec["s"] += (e - s) / 1e9 / n
        rec["n"] += 1 / n
        if prog == "accumulate" and COLLECTIVE.search(name):
            acc["collectives"]["s"] += (e - s) / 1e9 / n
            acc["collectives"]["n"] += 1 / n
        begin.append(max(s, lo))
        end.append(min(e, hi))
    busy = union(np.frombuffer(begin), np.frombuffer(end))
    acc["busy_s"] += float(np.sum(busy[:, 1] - busy[:, 0])) / 1e9 / n
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    for s, e in edges[edges[:, 1] > edges[:, 0]]:
        who = host.at((s + e) / 2)
        acc["gaps"][who] = acc["gaps"].get(who, 0.0) + (e - s) / 1e9 / n


def union(begin, end):
    """The union of intervals, as sorted disjoint (k, 2) rows."""
    if not len(begin):
        return np.zeros((0, 2))
    order = np.argsort(begin, kind="stable")
    b, e = begin[order], np.maximum.accumulate(end[order])
    new = np.concatenate([[True], b[1:] > e[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(b) - 1]])
    return np.stack([b[first], e[last]], axis=1)


def reduce(profile, device_ids) -> dict:
    """The reduced trace of the chips ``device_ids`` (see the module's
    docstring).  Device operations are read in start order, one at a
    time: a step's trace holds millions."""
    planes = list(profile.planes)
    host = HostSpans(planes)
    chips = {int(m.group(1)): p for p in planes
             if (m := re.fullmatch(r"/device:TPU:(\d+)", p.name))}
    missing = [d for d in device_ids if d not in chips]
    if missing:
        raise ValueError(f"no trace plane for TPU {missing}; planes: "
                         f"{[p.name for p in planes]}")
    if host.win is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span on the host")
    lo, hi = host.win
    n = len(device_ids)
    acc = {"programs": {}, "ops": {}, "collectives": {"s": 0.0, "n": 0},
           "busy_s": 0.0, "gaps": {}}
    for d in device_ids:
        _device(chips[d], lo, hi, host, n, acc)
    top_ops = sorted(((f"{p}:{o}", r["s"]) for (p, o), r in acc["ops"].items()
                      if not o.startswith(CONTAINERS)),
                     key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(acc["gaps"].items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (hi - lo) / 1e9, "busy_s": acc["busy_s"],
            "programs": acc["programs"], "ops": acc["ops"],
            "collectives": acc["collectives"],
            "breakdown": {"device_ops": [list(x) for x in top_ops],
                          "idle_gaps": [list(x) for x in top_gaps]}}


def reduce_dir(trace_dir: str, device_ids) -> dict:
    return reduce(load(trace_dir), device_ids)


def kernel_table(red: dict, kernel_calls: dict, peaks: dict,
                 counts: dict) -> dict:
    """Per kernel: device time (``s``), calls (``n``) and the least time
    its calls could take (``least_s``), from the HLO's Pallas calls
    (``kernel_calls``: program -> op name -> caller and shapes), the count
    function of each caller (``counts``: caller -> (kernel, count)) and
    the chip's peaks.  A kernel with no count function is left out."""
    out = {}
    for (prog, op), rec in red["ops"].items():
        call = kernel_calls.get(prog, {}).get(op)
        if call is None or call["caller"] not in counts:
            continue
        kernel, count = counts[call["caller"]]
        flops, bytes_ = count(call["operands"], call["results"])
        least = max(flops / peaks["flops_bf16"],
                    bytes_ / peaks["hbm_bytes_per_s"])
        k = out.setdefault(kernel, {"s": 0.0, "n": 0, "least_s": 0.0})
        k["s"] += rec["s"]
        k["n"] += rec["n"]
        k["least_s"] += least * rec["n"]
    return out
