"""A run at a test size, with the chip's look skipped: sound, it comes out
correct; with each fault a cell can have planted in its timed path, it
comes out not correct."""
import pytest

from _paths import FIXTURES

import cell
import control

SEED = 2**31 + 77
CASES = [(c, f) for c in ("tiny-vit.dp-stream", "tiny-vit.sgd",
                          "tiny-lm.dp-ghost")
         for f in (None, "unchanged", "half_batch", "token")] + [
    (c, "unsplit_key") for c in ("tiny-vit.dp-stream", "tiny-lm.dp-ghost")] + [
    # the fixture whose fit calls take more than one step
    ("tiny-vit.dp-stream", "skip_step")]


@pytest.mark.parametrize("name,fault", CASES)
def test_run_correct_only_when_sound(name, fault):
    spec = cell.load_spec(name, FIXTURES, FIXTURES)
    with control.planted(fault, spec):
        result = cell.run_cell(spec, SEED, 0.3, False, want_chip=False)
    assert result["correct"] is (fault is None), result["check"]
    assert result["metrics"]["examples_per_s"]["value"] > 0


@pytest.mark.parametrize("name", ["tiny-vit.dp-stream", "tiny-lm.dp-ghost"])
def test_control_is_not_correct(name):
    import check
    spec = cell.load_spec(name, FIXTURES, FIXTURES)
    limits = spec["cell"]["limits"]
    out = control.readings(spec, SEED, control=True)
    assert out["program_verdict"] == {"correct": True, "failed": []}
    # judged over the control's own numbers only, one of them over its limit
    assert set(out["control"]) == {"grad_gap", "grad_cos_gap", "change_gap",
                                   "eps_gap"}
    ok, rows = check.verdict(out["control"], limits, only=out["control"])
    assert not ok and any(v > lim for _, v, lim in rows), rows
    assert out["control_verdict"]["failed"]


X4 = """
import json, sys
sys.path[:0] = [{src!r}, {bench!r}]
import cell, control
spec = cell.load_spec("tiny-vit.dp-stream.x4", {fx!r}, {fx!r})
out = {{}}
for fault in (None, "no_exchange", "half_batch"):
    with control.planted(fault, spec):
        out[str(fault)] = cell.run_cell(spec, {seed}, 0.3, False,
                                        want_chip=False)["correct"]
print(json.dumps(out))
"""


def test_four_device_run_and_exchange_fault():
    """The four-chip layout on four host devices (a process of its own:
    the device count is fixed when JAX starts)."""
    import json
    import os
    import subprocess
    import sys
    from _paths import BENCH, ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = X4.format(src=os.path.join(ROOT, "src"), bench=BENCH,
                     fx=FIXTURES, seed=SEED)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"None": True, "no_exchange": False, "half_batch": False}
