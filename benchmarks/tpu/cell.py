#!/usr/bin/env python3
"""One benchmark cell of the DP-SGD trainer on TPU: a model configuration
under a training job, measured through the normal path.

    python benchmarks/tpu/cell.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>
    python benchmarks/tpu/cell.py --workload <cell> --rehearse

Everything a cell is comes from files found by name: ``BENCHMARK.json``
(cells, metrics), ``workloads/<cell>.json`` (the job), ``configs/<config>
.json`` (the model as run), ``metrics/<metric>.py`` (a per-layer reader)
and ``counts/<kernel>.py`` (a kernel's operations and bytes).

A run builds ``PrivacySession.from_config(..., launch=LaunchConfig(mesh=
(chips,), axes=("data",)))``, puts in weights made from the seed, and takes
the first ``check_steps`` logical steps through one ``fit`` call (which
compiles: that is the warm-up).  It then calls ``fit`` in chunks of whole
logical steps, each closed by ``block_until_ready`` on the state, until
``--seconds`` have passed.  ``examples_per_s`` counts the real
(unmasked) examples of the window's steps over its wall time, from the
reference's draws, which the check holds to the rows ``fit`` fetched;
``setup_s`` is the time from process start to the window.  With ``--trace 1`` the
window runs under the profiler and the per-layer metrics are printed
instead.  Afterwards the checked steps are compared with the plain
reference (``check.py``) and every compared number is printed beside its
limit: on standard error as its last lines, and under ``check``, last, in
the result line, which is the last line of standard output.

``--rehearse`` compiles the cell's accumulate and update for a described
v5e (one chip, or ``v5e:2x2`` for four) through the same session path and
prints each program's ``memory_analysis()`` and whether a Pallas kernel
(``tpu_custom_call``) is in it.

With no TPU, fewer chips than the cell asks for, or no ``src/repro`` in the
checkout, it exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE_DIR = os.path.join(HERE, ".trace")


class NoChip(RuntimeError):
    """The machine does not hold the chips the cell asks for."""


# ---------------------------------------------------------------------------
# the spec: files found by name
# ---------------------------------------------------------------------------

def load_spec(name: str, root: str = ROOT, base: str = HERE) -> dict:
    """The cell ``name``: its BENCHMARK.json entry (under ``root``), its
    workload and configuration files (under ``base``) and the metric
    entries that apply to it."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    entry = cells[name]
    with open(os.path.join(base, "workloads", f"{name}.json")) as f:
        cell = json.load(f)
    if cell["config"] != entry["config"] or cell["chips"] != entry["chips"]:
        raise ValueError(f"workloads/{name}.json disagrees with "
                         f"BENCHMARK.json on config or chips")
    with open(os.path.join(base, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)

    def applies(m):
        return name in m.get("workloads", [name])

    return {"name": name, "entry": entry, "cell": cell, "config": config,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under this directory, by file path (metric
    names hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def counts_by_caller() -> dict:
    """The jitted function that makes a ``pallas_call`` (``CALLERS`` of
    each ``counts/<kernel>.py``) -> (kernel, its count function)."""
    out = {}
    for f in sorted(os.listdir(os.path.join(HERE, "counts"))):
        if f.endswith(".py"):
            kernel = f[:-3]
            mod = load_module("counts", kernel)
            for caller in mod.CALLERS:
                out[caller] = (kernel, mod.count)
    return out


def load_peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"({sorted(table['devices'])}); add its published "
                       f"peaks with their source")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# building the session
# ---------------------------------------------------------------------------

def session_for(spec: dict, seed: int, obs=None):
    """The cell's PrivacySession, through the normal path."""
    from repro.configs.base import ArchConfig
    from repro.core import DPConfig
    from repro.core.session import PrivacySession, TrainConfig
    from repro.launch.executor import LaunchConfig

    cell, model = spec["cell"], spec["config"]["model"]
    private = cell["engine"] != "nonprivate"
    dp = DPConfig(engine=cell["engine"], clip_norm=cell["clip_norm"])
    tc = TrainConfig(
        steps=cell["calibration_steps"], n_data=cell["n_data"],
        seq_len=cell["seq_len"] or 16,
        physical_batch=cell["physical_batch"],
        q=cell["expected_batch"] / cell["n_data"], sampler=cell["sampler"],
        target_eps=cell["target_eps"] if private else None,
        lr=cell["lr"], optimizer=cell["optimizer"],
        momentum=cell["momentum"], smoke=False, seed=seed,
        log_every=1 << 40)
    launch = LaunchConfig(mesh=(cell["chips"],), axes=("data",),
                          layout=cell["layout"])
    return PrivacySession.from_config(ArchConfig(**model), dp, tc,
                                      launch=launch, obs=obs)


def seed_weights(session, spec: dict, seed: int):
    """Replace the session's parameters with the benchmark's own, made
    from the seed on the device in one call; returns the maker (the
    reference calls it again after the window)."""
    import jax
    from pools import make_weights

    shapes = jax.eval_shape(lambda: session.state.params)
    init = spec["config"]["init"]

    def make():
        return make_weights(shapes, init, seed)

    session.state = session.executor.place_state(
        session.state._replace(params=make()))
    return make


def host(x):
    import jax
    import numpy as np
    return jax.tree.map(np.asarray, jax.device_get(x))


class CompileCounter:
    """Counts programs compiled, or read from the persistent cache, while
    ``armed``."""

    def __init__(self):
        import jax
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in (
                "/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.count += 1


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

def check_chips(spec: dict) -> None:
    import jax
    chips = spec["cell"]["chips"]
    n = len(jax.devices())
    if jax.default_backend() != "tpu" or n < chips:
        raise NoChip(f"cell {spec['name']} needs {chips} TPU chip(s); JAX "
                     f"sees {n} {jax.default_backend()} device(s)")


def set_up(spec: dict, seed: int, obs=None) -> dict:
    """The session with the seed's weights and pool, driven through the
    checked steps: one call of the window's own ``fit`` and feed.  The
    update is watched from outside to copy what the check needs to the
    host (each step's key, the momentum after step 1); the time of those
    copies is returned apart (``copy_s``), since set-up without the check
    would not make them.  The pool keeps recording what ``fit`` fetches."""
    import jax
    from pools import leaf_paths, make_pool

    cell, config = spec["cell"], spec["config"]
    if not (cell["optimizer"] == "sgd" and cell["momentum"] > 0):
        raise ValueError("the check reads the first gradient from SGD's "
                         "momentum buffer; the cell has none")
    session = session_for(spec, seed, obs)
    make_params = seed_weights(session, spec, seed)
    pool = make_pool(cell["pool"], config["model"], cell["seq_len"],
                     cell["n_data"], seed)
    run = {"seed": seed, "rngs": []}
    copy_s = [0.0]
    jitted = type(session)._jitted

    def watched(name):
        fn = jitted(session, name)
        if name != "update":
            return fn

        def update(state):
            t0 = time.perf_counter()
            run["rngs"].append(host(state.rng))
            copy_s[0] += time.perf_counter() - t0
            state = fn(state)
            if "mom1" not in run:
                t0 = time.perf_counter()
                run["mom1"] = host(state.opt_state["mom"])
                copy_s[0] += time.perf_counter() - t0
            return state
        return update

    K = cell["check_steps"]
    pool.record = []
    session._jitted = watched
    try:
        session.fit(pool, steps=K)
    finally:
        del session._jitted
    t0 = time.perf_counter()
    # the key of the step after the checked ones: its noise is replayed too
    run["rngs"].append(host(session.state.rng))
    run["steps"] = K
    run["step_gap"] = abs(int(session.state.step) - K)
    run["params_k"] = host(session.state.params)
    run["eps"] = float(session.privacy_spent()[0])
    run["sigma"] = float(session.dp.noise_multiplier)
    run["leaf_shapes"] = {p: x.shape for p, x in zip(
        leaf_paths(run["params_k"]), jax.tree.leaves(run["params_k"]))}
    jax.block_until_ready(session.state)
    copy_s[0] += time.perf_counter() - t0
    return {"session": session, "pool": pool, "make_params": make_params,
            "run": run, "copy_s": copy_s[0]}


def window(spec: dict, session, pool, seconds: float, trace: bool) -> dict:
    """Calls ``fit`` in chunks of whole logical steps, each closed by
    ``block_until_ready`` on the state, until ``seconds`` have passed."""
    import jax

    cell = spec["cell"]
    counter = CompileCounter()
    spc = cell["steps_per_call"]
    fetches = len(pool.record)
    if trace:
        # host spans come from TraceAnnotations (the program's obs spans,
        # the window's and the pool's own); the Python tracer stays off so
        # that the traced run's host keeps its pace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        pool.annotate = True
    span = jax.profiler.TraceAnnotation("bench/window") if trace \
        else contextlib.nullcontext()
    counter.armed = True
    steps = 0
    with span:
        t0 = time.perf_counter()
        while True:
            session.fit(pool, steps=spc)
            steps += spc
            # fit returns with its last step still on the device; the next
            # call would wait for it first in any case
            jax.block_until_ready(session.state)
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    counter.armed = False
    if trace:
        jax.profiler.stop_trace()
        pool.annotate = False
    return {"window_s": window_s, "steps": steps,
            "physical_rows": sum(len(x) for x in pool.record[fetches:]),
            "compiles": counter.count}


def read_noise(spec: dict, session, run: dict) -> None:
    """The noise each checked step, and the step after them, drew: the
    compiled update again, from the step's own key, on a zeroed
    accumulator and momentum, which leaves sigma*C*z/L in the momentum.
    Takes the session's state (the window is over)."""
    import jax.numpy as jnp
    if spec["cell"]["engine"] == "nonprivate":
        return
    state, session.state = session.state, None
    run["noise"] = []
    for rng in run["rngs"]:
        zeros = jnp.zeros_like(state.grad_acc)
        state = state._replace(
            grad_acc=zeros, rng=jnp.asarray(rng),
            opt_state=dict(state.opt_state, mom=jnp.zeros_like(zeros)))
        del zeros
        state = session._jitted("update")(session.executor.place_state(state))
        run["noise"].append(host(state.opt_state["mom"]))


def program_peak(compiled) -> int:
    """Bytes a compiled program holds on a chip while it runs: arguments
    and outputs not aliased to them, and its temporaries."""
    ma = compiled.memory_analysis()
    if ma is None:
        return 0
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def params_finite(session) -> bool:
    import jax
    import numpy as np
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(jax.device_get(session.state.params)))


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             *, want_chip: bool = True) -> dict:
    """One run of a cell: set-up, the checked steps, the window, the
    comparison.  Returns the result object (printed by :func:`main`)."""
    import jax
    import numpy as np
    import check
    import flops

    if want_chip:
        check_chips(spec)
    cell, config = spec["cell"], spec["config"]
    chips = cell["chips"]
    obs = None
    if trace:
        from repro.obs import ObsConfig
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        obs = ObsConfig(mode="events", profile_dir=TRACE_DIR)
    up = set_up(spec, seed, obs)
    session, pool, run = up["session"], up["pool"], up["run"]
    make_params = up["make_params"]
    setup_s = time.perf_counter() - T_START - up["copy_s"]

    counters = window(spec, session, pool, seconds, trace)
    run["compiles"] = counters["compiles"]
    run["steps"] += counters["steps"]
    run["step_gap"] = abs(int(session.state.step) - run["steps"])
    run["fetched"] = pool.record
    pool.record = None
    draw = _draw(cell["sampler"])
    q = cell["expected_batch"] / cell["n_data"]
    counters["examples"] = sum(
        len(draw(seed, k, cell["n_data"], q))
        for k in range(run["steps"] - counters["steps"], run["steps"]))
    counters["examples_per_s"] = counters["examples"] / counters["window_s"]
    finite = params_finite(session)
    used = jax.devices()[:chips]
    counters["chips"] = chips
    counters["flops_per_example"] = flops.per_example(
        config["model"], run["leaf_shapes"], cell["seq_len"])

    # the programs the window drives, as fit jits them (from the cache)
    p = cell["physical_batch"]
    batch, mask = pool.rows(np.arange(p)), np.ones(p, np.float32)
    programs = {"accumulate": session.compiled("accumulate", batch, mask),
                "update": session.compiled("update")}
    counters["program_peak_bytes"] = max(
        program_peak(c) for c in programs.values())
    runtime_peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
                       for d in used) if want_chip else 0
    kernel_calls = {}
    if trace:
        import hlo
        kernel_calls = {name: hlo.custom_calls(c.as_text())
                        for name, c in programs.items()}
    del programs
    read_noise(spec, session, run)
    del session, up
    gc.collect()

    t0 = time.perf_counter()
    values = check.readings(run, cell, config, pool, make_params, draw)
    values["detail"]["check_s"] = time.perf_counter() - t0
    for k, v in values.pop("detail").items():
        print(f"detail {k}: {v!r}", file=sys.stderr, flush=True)
    correct, rows_checked = check.verdict(values, cell.get("limits", {}))

    dev = jax.devices()[0]
    result = {"correct": correct and finite, "attempted": counters["steps"],
              "failed": 0 if finite else counters["steps"]}
    # the runtime's peak leaves out the programs' temporaries
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": max(
                  runtime_peak, counters["program_peak_bytes"])}
    if trace:
        tr = load_local("trace")
        red = tr.reduce_dir(TRACE_DIR, [d.id for d in used])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        red["kernels"] = tr.kernel_table(red, kernel_calls,
                                         load_peaks(dev.device_kind),
                                         counts_by_caller())
        result["metrics"] = per_layer(spec, red, counters, dev.device_kind)
        result["breakdown"] = red["breakdown"]
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    else:
        result["metrics"] = {
            "examples_per_s": {"value": counters["examples_per_s"],
                               "unit": "examples/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["device"] = device
    result["check"] = {n: {"value": v, "limit": lim}
                       for n, v, lim in rows_checked}
    result["check"]["params_finite"] = {"value": finite, "limit": True}
    return result


def load_local(name: str):
    """A module of this directory by file path (``trace`` would otherwise
    meet the standard library's module of that name)."""
    path = os.path.join(HERE, f"{name}.py")
    mspec = importlib.util.spec_from_file_location(f"bench_{name}", path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def _draw(sampler: str):
    import importlib
    return importlib.import_module(f"reference.sampler_{sampler}").draw


def per_layer(spec: dict, red: dict, counters: dict, kind: str) -> dict:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing returns None and the metric is left out."""
    cell = dict(spec["cell"], name=spec["name"],
                config=spec["config"], peaks=load_peaks(kind))
    out = {}
    for m in spec["per_layer"]:
        value = load_module("metrics", m["name"]).read(red, counters, cell)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# rehearsal: compile for a described v5e
# ---------------------------------------------------------------------------

def rehearse(spec: dict) -> dict:
    """Compile the cell's accumulate and update for a described v5e
    through the session path; sizes only, nothing runs.

    The session is asked for its programs as it builds them; what it would
    place on the chips is kept as shapes: the model's init and the train
    state are evaluated abstractly, and its mesh is built from the
    described chips.  Code that asks ``jax.default_backend()`` sees the CPU
    here, so the kernels are told they compile."""
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import repro.core.fused as fused
    import repro.core.layers as layers
    import repro.core.session as sess
    import repro.kernels.ops as ops
    import repro.models as models
    from repro.launch import executor as ex

    chips = spec["cell"]["chips"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    described = Mesh(np.array(topo.devices[:chips]), ("data",))
    rep = NamedSharding(described, P())

    def shapes_build(cfg):
        model = build(cfg)
        init = model.init
        model.init = lambda key: jax.eval_shape(init, key)
        return model

    patches = [
        (fused, "interpret_mode", lambda: False),
        (layers, "interpret_mode", lambda: False),
        (ops, "interpret_mode", lambda: False),
        (models, "build", shapes_build),
        (sess, "init_state", lambda p, o, r: jax.eval_shape(
            lambda p, r: init_state(p, o, r), p, r)),
        (ex.LaunchConfig, "build_mesh", lambda self: described),
        (ex.MeshExecutor, "_donate", lambda self, argnums: argnums),
        (ex.MeshExecutor, "place_state", lambda self, st: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            st)),
    ]
    build, init_state = models.build, sess.init_state
    saved = [(obj, attr, getattr(obj, attr)) for obj, attr, _ in patches]
    for obj, attr, val in patches:
        setattr(obj, attr, val)
    try:
        session = session_for(spec, 0)
        state = session.state
        cell = spec["cell"]
        p = cell["physical_batch"]
        bs = session.executor.batch_sharding(p)
        batch = {k: jax.ShapeDtypeStruct((p,) + v[0], v[1], sharding=bs)
                 for k, v in pool_shapes(spec).items()}
        mask = jax.ShapeDtypeStruct((p,), np.float32, sharding=bs)
        session._configure_train()
        out = {}
        for prog, args in (("accumulate", (state, batch, mask)),
                           ("update", (state,))):
            compiled = session._jitted(prog).lower(*args).compile()
            ma = compiled.memory_analysis()
            text = compiled.as_text()
            out[prog] = {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "peak_bytes": program_peak(compiled),
                "tpu_custom_call": "tpu_custom_call" in text,
                "all_reduce": "all-reduce" in text}
        return out
    finally:
        for obj, attr, val in saved:
            setattr(obj, attr, val)


def pool_shapes(spec: dict) -> dict:
    """Per-row shape and dtype of the cell's data."""
    import ml_dtypes
    import numpy as np
    cell, model = spec["cell"], spec["config"]["model"]
    if cell["pool"]["kind"] == "images":
        s = model["image_size"]
        return {"image": ((s, s, 3), ml_dtypes.bfloat16),
                "label": ((), np.int32)}
    t = cell["seq_len"]
    return {"tokens": ((t,), np.int32), "labels": ((t,), np.int32)}


# ---------------------------------------------------------------------------

def report(result: dict) -> None:
    for name, row in result["check"].items():
        print(f"check {name}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="compile for a described v5e and print sizes")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"cell.py: no src/repro in {ROOT}; run it from a checkout of "
              f"the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    sys.path.insert(0, HERE)
    spec = load_spec(args.workload)

    # the TPU runtime's logs would go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearse:
        print(json.dumps({"workload": args.workload,
                          "rehearsal": rehearse(spec)}), flush=True)
        return 0

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, comes from the cache after
    # the first run, so that set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"cell.py: {e}", file=sys.stderr)
        return 1
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
