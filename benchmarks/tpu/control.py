#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip at the cell's
own size (the benchmark's own runs do not run this):

    python benchmarks/tpu/control.py --workload <cell> --seeds 1 2 3 \\
        [--control] [--fault <one of FAULTS>]

For each seed it builds the cell's session exactly as a run does, takes the
checked steps (no measured window: training's readings need none), and
prints one JSON line with the compared numbers, and the verdict of the
cell's limits over them (``verdict``: correct, and the names that fail), of

* ``program``: the program as it stands, or with ``--fault`` planted in
  its timed path;
* ``control`` (``--control``): the plain reference computed with int8
  matmul operands (the precision below the configuration's bfloat16) put
  in the program's place, against the float32 reference; eps is the
  reference accountant in float32 against float64.

The faults:

* ``unchanged``: the update returns its state unchanged;
* ``unsplit_key``: the update hands its input key on instead of the split
  one, so that every step draws the same noise;
* ``skip_step``: ``fit`` takes one logical step fewer than it is asked
  for, in calls of more than one;
* ``half_batch``: the second half of every physical batch is masked out,
  so the step averages over the rest;
* ``no_exchange``: the sum across chips inside the kernels is left out
  (cells on more than one chip);
* ``token``: the labels of the first example of every physical batch
  (its class, or every next-token label of its row) are altered where the
  batch is produced.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

FAULTS = ("unchanged", "unsplit_key", "skip_step", "half_batch",
          "no_exchange", "token")


@contextlib.contextmanager
def planted(fault, spec: dict):
    """The timed path with ``fault`` planted underneath (None: as is)."""
    if fault is None:
        yield
        return
    import numpy as np
    from repro.core.session import PrivacySession
    from repro.launch.executor import MeshExecutor
    from pools import PoolDataset

    saved = []

    def patch(obj, attr, val):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, val)

    def wrap_program(program, make):
        """``program`` of every session replaced by ``make(fn)``; the
        replacement keeps ``fn.lower``, so sizes are read from the sound
        program."""
        jitted = PrivacySession._jitted

        def wrapped(self, name):
            fn = jitted(self, name)
            if name != program:
                return fn
            call = make(fn)
            call.lower = fn.lower
            return call
        patch(PrivacySession, "_jitted", wrapped)

    if fault == "unchanged":
        wrap_program("update", lambda fn: lambda state: state)
    elif fault == "unsplit_key":
        def unsplit(fn):
            def call(state):
                import jax
                key = jax.device_get(state.rng)
                out = fn(state)
                return out._replace(
                    rng=jax.device_put(key, out.rng.sharding))
            return call
        wrap_program("update", unsplit)
    elif fault == "skip_step":
        fit = PrivacySession.fit

        def short(self, dataset=None, steps=None, **kw):
            if steps is not None and steps > 1:
                steps -= 1
            return fit(self, dataset, steps, **kw)
        patch(PrivacySession, "fit", short)
    elif fault == "half_batch":
        def half(fn):
            def call(state, batch, mask):
                import jax
                keep = np.arange(mask.shape[0]) < mask.shape[0] // 2
                keep = jax.device_put(keep.astype(np.float32), mask.sharding)
                return fn(state, batch, mask * keep)
            return call
        wrap_program("accumulate", half)
    elif fault == "no_exchange":
        kmap = MeshExecutor._kernel_map

        def no_psum(self, fn, rows=0):
            import jax
            psum = jax.lax.psum
            mapped = kmap(self, fn, rows)

            def call(*args):
                jax.lax.psum = lambda x, axes: x
                try:
                    return mapped(*args)
                finally:
                    jax.lax.psum = psum
            return call
        patch(MeshExecutor, "_kernel_map", no_psum)
    elif fault == "token":
        fetch = PoolDataset.fetch
        model = spec["config"]["model"]

        def altered(self, idx):
            out = dict(fetch(self, idx))
            if "label" in out:
                lab = out["label"].copy()
                lab[0] = (lab[0] + 1) % model["n_classes"]
                out["label"] = lab
            else:
                lab = out["labels"].copy()
                lab[0] = (lab[0] + 1) % model["vocab"]
                out["labels"] = lab
            return out
        patch(PoolDataset, "fetch", altered)
    else:
        raise ValueError(f"unknown fault {fault!r}; faults: {FAULTS}")
    try:
        yield
    finally:
        for obj, attr, val in reversed(saved):
            setattr(obj, attr, val)


def readings(spec: dict, seed: int, *, fault=None, control=False) -> dict:
    """The compared numbers of one seed's checked steps (no window)."""
    import cell as harness
    import check

    with planted(fault, spec):
        up = harness.set_up(spec, seed)
        session, run = up["session"], up["run"]
        run["fetched"] = up["pool"].record
        up["pool"].record = None
        harness.read_noise(spec, session, run)
    del session
    up.pop("session")
    gc.collect()
    c, cfg = spec["cell"], spec["config"]
    draw = harness._draw(c["sampler"])
    values = check.readings(run, c, cfg, up["pool"], up["make_params"],
                            draw, control=control)
    out = {"seed": seed, "fault": fault}
    if control:
        out["control"] = values.pop("control")
    out["detail"] = values.pop("detail")
    out["program"] = values
    for side in ("program", "control"):
        if side in out:
            ok, rows = check.verdict(out[side], c["limits"], only=out[side])
            out[f"{side}_verdict"] = {"correct": ok, "failed": [
                n for n, v, lim in rows
                if not check.verdict({n: v}, {n: lim})[0]]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=FAULTS)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import cell as harness
    from repro.launch.compile_cache import enable_compile_cache

    spec = harness.load_spec(args.workload)
    try:
        harness.check_chips(spec)
    except harness.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in args.seeds:
        print(json.dumps(readings(spec, seed, fault=args.fault,
                                  control=args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
