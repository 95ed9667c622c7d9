"""The benchmark's files agree with each other: every cell names a
configuration, engine and sampler that exist, every per-layer metric has
its reader, and the peaks table refuses a device it does not know."""
import json
import os

import pytest

from _paths import BENCH, ROOT

import cell


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells():
    return [w["name"] for w in _bench()["workloads"]]


@pytest.mark.parametrize("name", _cells())
def test_cell_files(name):
    from repro.core.clipping import available_engines
    from repro.data.sampler import available_samplers
    spec = cell.load_spec(name)
    c = spec["cell"]
    assert c["engine"] in available_engines() + ("nonprivate",)
    assert c["sampler"] in available_samplers()
    assert os.path.exists(os.path.join(
        BENCH, "reference", f"sampler_{c['sampler']}.py"))
    assert spec["config"]["name"] == c["config"]
    assert os.path.exists(os.path.join(
        BENCH, "reference", f"{spec['config']['reference']}.py"))
    assert c["chips"] == spec["entry"]["chips"]
    # every number the check compares has its limit
    names = {"sample_mismatch", "step_gap", "grad_gap", "grad_cos_gap",
             "change_gap", "window_compiles"}
    if c["engine"] != "nonprivate":
        names |= {"noise_std_err", "noise_corr", "eps_gap"}
    assert set(c["limits"]) == names


def test_metrics_have_readers():
    bench = _bench()
    cells = set(_cells())
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py")), m["name"]
        assert set(m["workloads"]) <= cells
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_peaks_refuse_unknown_kind():
    assert cell.load_peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        cell.load_peaks("TPU v9 imaginary")
