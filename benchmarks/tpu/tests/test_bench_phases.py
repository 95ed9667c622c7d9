"""The phase split: the program's named scopes read from the HLO of the
fixture cells compiled for a described v5e, device time summed by phase
and idle time by host span on a small recorded trace
(fixtures/trace_input.pbtxt), and the readers of the phase and input
metrics."""
import os

import pytest

from _paths import FIXTURES

import cell
import hlo
import phase_split


# -- the vocabulary and the HLO ----------------------------------------------

def test_vocabulary_is_the_programs():
    from repro.obs.phases import PHASES
    assert phase_split.PHASES == PHASES


@pytest.mark.parametrize("op_name,phase", [
    ("jit(accumulate)/while/body/grad/per_example/vmap(transpose(jvp()))"
     "/dot_general", "grad/per_example"),
    ("jit(accumulate)/while/body/grad/accumulate/jit(clip_accum_inplace)"
     "/pallas_call", "grad/accumulate"),
    ("jit(accumulate)/grad/per_example/transpose(jvp(grad/accumulate))/mul",
     "grad/accumulate"),
    ("jit(accumulate)/grad/norm_pass", "grad/norm_pass"),
    ("jit(accumulate)/grad/batches/mul", None),
    ("jit(accumulate)/xgrad/batch/mul", None),
    ("jit(update)/jit(noisy_sgd_update)/pallas_call", None),
])
def test_innermost_phase(op_name, phase):
    assert phase_split.innermost_phase(op_name) == phase


HLO = (
    '  %fusion.7 = f32[8,64]{1,0} fusion(%p), kind=kOutput, metadata={'
    'op_name="jit(accumulate)/while/body/grad/per_example/vmap(jvp())/'
    'dot_general" source_file="x.py" source_line=3}\n'
    '  %copy.2 = f32[64]{0} copy(%q)\n'
    '  ROOT %add.170 = f32[64]{0} add(%a, %b), metadata={op_name='
    '"jit(accumulate)/grad/accumulate/add"}\n')


def test_op_phases():
    assert phase_split.op_phases(HLO) == {"fusion.7": "grad/per_example",
                                          "add.170": "grad/accumulate"}


@pytest.fixture(scope="module")
def described():
    """A described v5e to compile for, with the persistent cache off: a
    compile for a described chip is written to it but cannot be read back
    without one."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name,phases,kernels", [
    ("tiny-vit.dp-stream", {"grad/per_example", "grad/accumulate"},
     {"clip_accum_inplace"}),
    ("tiny-lm.dp-ghost", {"grad/norm_pass", "grad/reweighted",
                          "grad/accumulate"}, {"ghost_norm_dense"}),
    ("tiny-vit.sgd", {"grad/batch", "grad/accumulate"}, set()),
])
def test_rehearsed_programs_carry_phases(described, monkeypatch, name,
                                         phases, kernels):
    """The accumulate and update as ``cell.py --rehearse`` compiles them
    for a described v5e: the accumulate's instructions carry the engine's
    phases (and no other), the update's none, and every Pallas call still
    resolves to its caller, which the kernels' rooflines need."""
    import repro.core.layers as layers
    # the tiny model's T^2 <= d_in * d_out picks the ghost path everywhere;
    # the direct path is the one that runs the ghost_norm kernel
    monkeypatch.setattr(layers, "_FORCE_PATH", "direct")
    texts = {}
    peak = cell.program_peak

    def keep(compiled):
        texts["accumulate" if not texts else "update"] = compiled.as_text()
        return peak(compiled)

    monkeypatch.setattr(cell, "program_peak", keep)
    cell.rehearse(cell.load_spec(name, FIXTURES, FIXTURES))
    assert set(phase_split.op_phases(texts["accumulate"]).values()) == phases
    assert phase_split.op_phases(texts["update"]) == {}
    callers = {prog: {c["caller"] for c in hlo.custom_calls(t).values()}
               for prog, t in texts.items()}
    assert callers == {"accumulate": kernels, "update": {"noisy_sgd_update"}}


# -- the trace ----------------------------------------------------------------

@pytest.fixture(scope="module")
def profile():
    import jax
    with open(os.path.join(FIXTURES, "trace_input.pbtxt")) as f:
        text = "\n".join(l for l in f if not l.startswith("#"))
    return jax.profiler.ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def red(profile):
    return cell.load_local("trace").reduce(profile, [0])


def test_phase_table(red):
    phases = {"accumulate": {"fusion.1": "grad/per_example",
                             "clip_accum_inplace.3": "grad/accumulate",
                             "while.2": "grad/per_example"}}
    table = phase_split.phase_table(red, phases)
    # the while op overlaps its body and is left out; the copy has no phase
    assert table == {"accumulate": {
        "grad/per_example": {"s": pytest.approx(6e-3), "n": 2},
        "grad/accumulate": {"s": pytest.approx(4e-3), "n": 2}}}


def test_idle_under_counts_every_depth(profile, red):
    under = phase_split.idle_under(profile, [0])
    ms = {k: round(v * 1e3, 9) for k, v in under.items()}
    assert ms == {"bench/window": 7.0, "fit/accumulate": 4.5,
                  "fit/sample": 0.5, "fit/fetch": 2.5, "input/fetch": 1.6,
                  "fit/place": 1.0, "fit/update": 1.0}
    assert under["bench/window"] == pytest.approx(red["window_s"]
                                                  - red["busy_s"])
    # the reduction names each gap by the innermost span at its middle, so
    # the pool's input/fetch hides the program's fit/fetch around it
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert "fit/fetch" not in gaps and gaps["input/fetch"] > 0
    assert under["fit/fetch"] > under["input/fetch"]


# -- the readers ----------------------------------------------------------------

def _red(**kw):
    red = {"programs": {"accumulate": {"s": 0.4, "n": 4}},
           "phases": {"accumulate": {
               "grad/norm_pass": {"s": 0.1, "n": 8},
               "grad/reweighted": {"s": 0.2, "n": 8},
               "grad/accumulate": {"s": 0.08, "n": 8}}},
           "idle_under": {"fit/sample": 0.001, "fit/fetch": 0.004,
                          "fit/place": 0.001, "input/fetch": 0.003,
                          "fit/update": 0.5}}
    red.update(kw)
    return red


@pytest.mark.parametrize("metric,value", [
    ("engine.grad_ms", 75.0), ("engine.norm_pass_ms", 25.0),
    ("engine.fold_ms", 20.0), ("input.stall_ms", 3.0)])
def test_readers(metric, value):
    read = cell.load_module("metrics", metric).read
    assert read(_red(), {"steps": 2}, {}) == pytest.approx(value)
    # a program without the scopes and spans: the metric is left out
    assert read(_red(phases={}, idle_under={}), {"steps": 2}, {}) is None
    bare = {"programs": {"accumulate": {"s": 0.4, "n": 4}}}
    assert read(bare, {"steps": 2}, {}) is None


def test_norm_pass_absent_outside_ghost():
    red = _red(phases={"accumulate": {
        "grad/per_example": {"s": 0.3, "n": 4},
        "grad/accumulate": {"s": 0.1, "n": 4}}})
    assert cell.load_module("metrics", "engine.norm_pass_ms").read(
        red, {"steps": 2}, {}) is None
    assert cell.load_module("metrics", "engine.grad_ms").read(
        red, {"steps": 2}, {}) == pytest.approx(75.0)
