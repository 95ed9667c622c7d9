"""The trace reduction on a small recorded trace (fixtures/trace.pbtxt):
busy union, idle gaps and what the host did in them, program and kernel
sums, and the Pallas calls read from HLO text."""
import os

import pytest

from _paths import FIXTURES

import cell
import hlo


@pytest.fixture(scope="module")
def red():
    import jax
    with open(os.path.join(FIXTURES, "trace.pbtxt")) as f:
        text = "\n".join(l for l in f if not l.startswith("#"))
    profile = jax.profiler.ProfileData.from_text_proto(text)
    return cell.load_local("trace").reduce(profile, [0])


def test_window_and_busy(red):
    assert red["window_s"] == pytest.approx(15e-3)
    # ops: [0, 4.5] + [6, 11] + [12, 14] ms
    assert red["busy_s"] == pytest.approx(11.5e-3)


def test_programs_and_collectives(red):
    assert red["programs"]["accumulate"] == {"s": pytest.approx(10e-3),
                                             "n": 2}
    assert red["programs"]["update"] == {"s": pytest.approx(2e-3), "n": 1}
    assert red["ops"][("accumulate", "clip_accum_inplace.3")]["n"] == 2
    assert red["ops"][("update", "noisy_sgd_update.1")]["s"] == \
        pytest.approx(2e-3)
    assert red["collectives"] == {"s": pytest.approx(0.5e-3), "n": 1}


def test_idle_gaps_named_by_host_span(red):
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps == {"input/fetch": pytest.approx(1.5e-3),
                    "fit/update": pytest.approx(1e-3),
                    "bench/window": pytest.approx(1e-3)}
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["accumulate:clip_accum_inplace.3"] == pytest.approx(5e-3)


def test_kernel_table(red):
    calls = {
        "accumulate": {"clip_accum_inplace.3": {
            "caller": "clip_accum_inplace",
            "operands": [((1, 1024), 4), ((16, 1024), 2), ((16, 1), 4),
                         ((16, 1), 4), ((1,), 4), ((1,), 4)],
            "results": [((1, 1024), 4)]}},
        "update": {"noisy_sgd_update.1": {
            "caller": "noisy_sgd_update",
            "operands": [((2,), 4), ((8, 128), 4), ((8, 128), 4),
                         ((8, 128), 4), ((1, 4), 4)],
            "results": [((8, 128), 4), ((8, 128), 4)]}}}
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    k = cell.load_local("trace").kernel_table(red, calls, peaks,
                                             cell.counts_by_caller())
    clip_bytes = 16 * 1024 * 2 + 2 * 1024 * 4
    assert k["clip_accum"]["n"] == 2
    assert k["clip_accum"]["s"] == pytest.approx(5e-3)
    assert k["clip_accum"]["least_s"] == pytest.approx(2 * clip_bytes / 1e9)
    assert k["noisy_update"]["least_s"] == pytest.approx(5 * 8 * 128 * 4
                                                         / 1e9)


HLO = (
    '  %clip_accum_inplace.9 = f32[1,1024]{1,0:T(1,128)} custom-call('
    '%broadcast.988, %pad.50, %copy-done.90, %copy-done.91, %gte.4853, '
    '/*index=5*/%gte.4835), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={f32[1,1024]{1,0}, f32[8,1024]{1,0}, '
    'f32[8,1]{1,0}, f32[8,1]{1,0}, f32[1]{0}, s32[1]{0}}, '
    'output_to_operand_aliasing={{}: (0, {})}, frontend_attributes='
    '{kernel_metadata={}}, metadata={op_name="jit(accumulate)/while/body/'
    'closed_call/jit(clip_accum_inplace)/pallas_call" stack_frame_id=245}, '
    'backend_config={"custom_call_config":{"body":"TUzv"}}\n'
    '  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop\n'
    '  %noisy_sgd_update.6 = (f32[32,128]{1,0:T(8,128)S(1)}, '
    'f32[32,128]{1,0:T(8,128)S(1)}) custom-call(%gte.110, %pbf.42, '
    '%pbf.41, %pbf.40, %constant.45), custom_call_target="tpu_custom_call",'
    ' operand_layout_constraints={s32[2]{0}, f32[32,128]{1,0}, '
    'f32[32,128]{1,0}, f32[32,128]{1,0}, f32[1,4]{1,0}}, '
    'frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(update)'
    '/jit(noisy_sgd_update)/pallas_call" stack_frame_id=39}\n')


def test_hlo_custom_calls():
    calls = hlo.custom_calls(HLO)
    assert set(calls) == {"clip_accum_inplace.9", "noisy_sgd_update.6"}
    c = calls["clip_accum_inplace.9"]
    assert c["caller"] == "clip_accum_inplace"
    assert c["results"] == [((1, 1024), 4)]
    assert c["operands"] == [((1, 1024), 4), ((8, 1024), 4), ((8, 1), 4),
                             ((8, 1), 4), ((1,), 4), ((1,), 4)]
    u = calls["noisy_sgd_update.6"]
    assert u["caller"] == "noisy_sgd_update"
    assert u["results"] == [((32, 128), 4)] * 2
    assert u["operands"] == [((2,), 4)] + [((32, 128), 4)] * 3 + [((1, 4), 4)]
