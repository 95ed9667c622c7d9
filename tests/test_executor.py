"""Executor layer: LaunchConfig resolution, sharded fit parity, dryrun schema.

jax locks the device count on first init, so multi-device cases run in a
subprocess with xla_force_host_platform_device_count=8 (same discipline as
test_mesh_sharding.py).  CI also runs this file directly under that flag, so
the in-process mesh tests execute there too.

Determinism contract (see launch/executor.py): a MeshExecutor fit in the dp
layout spends a bit-identical eps and matches LocalExecutor params to
reduction-order ULPs; strict bitwise equality is impossible on XLA:CPU
because LLVM contracts mul+add into FMAs per fusion, so the clipped-gradient
sum rounds differently depending on how the batch axis is split.
"""
import json

import jax
import pytest

from repro.launch.executor import (LaunchConfig, LocalExecutor, MeshExecutor,
                                   build_executor)


# -- LaunchConfig resolution (no devices needed) ----------------------------

def test_launch_config_presets():
    assert LaunchConfig().is_local
    assert LaunchConfig(mesh="local").is_local
    assert LaunchConfig(mesh="test").mesh_shape() == {"data": 2, "model": 2}
    assert LaunchConfig(mesh="production").mesh_shape() == \
        {"data": 16, "model": 16}
    assert LaunchConfig(mesh="production-multipod").mesh_shape() == \
        {"pod": 2, "data": 16, "model": 16}


def test_launch_config_explicit_shapes():
    assert LaunchConfig(mesh=(8,)).mesh_shape() == {"data": 8}
    assert LaunchConfig(mesh=(4, 2)).mesh_shape() == {"data": 4, "model": 2}
    assert LaunchConfig(mesh=(2, 4, 2)).mesh_shape() == \
        {"pod": 2, "data": 4, "model": 2}
    assert LaunchConfig(mesh=(3, 5), axes=("x", "y")).mesh_shape() == \
        {"x": 3, "y": 5}


def test_launch_config_rejects_bad_input():
    with pytest.raises(ValueError, match="preset"):
        LaunchConfig(mesh="bogus").validate()
    with pytest.raises(ValueError, match="axis names"):
        LaunchConfig(mesh=(2, 2, 2, 2)).validate()
    with pytest.raises(ValueError, match="layout"):
        LaunchConfig(mesh="test", layout="bogus").validate()


def test_build_executor_dispatch():
    assert isinstance(build_executor(None), LocalExecutor)
    assert isinstance(build_executor(LaunchConfig()), LocalExecutor)
    with pytest.raises(ValueError, match="local"):
        MeshExecutor(LaunchConfig())


def test_build_mesh_insufficient_devices_hint():
    """Too few devices must fail with the XLA_FLAGS remedy, not an opaque
    make_mesh error (e.g. an exported 8-device flag + the production mesh)."""
    if len(jax.devices()) >= 256:
        pytest.skip("host actually has 256+ devices")
    with pytest.raises(RuntimeError,
                       match="xla_force_host_platform_device_count=256"):
        LaunchConfig(mesh="production").build_mesh()


def test_mesh_executor_rejects_unknown_axes():
    """Custom axis names are fine for mesh_shape() cost descriptions, but
    the executor's sharding rules only know pod/data/model — fail at
    construction, not with a KeyError mid-fit."""
    with pytest.raises(ValueError, match="sharding rules"):
        MeshExecutor(LaunchConfig(mesh=(3, 5), axes=("x", "y")))


def test_local_executor_describe_and_constraints():
    import jax.numpy as jnp
    ex = LocalExecutor()
    assert ex.describe() == {"executor": "local"}
    c = ex.constraints("masked_pe")
    assert c.grad is None and c.pe_grad is None and c.pe_dtype is None
    # pe_bf16 is meaningful unsharded too (per-example grad storage dtype)
    cb = build_executor(LaunchConfig(pe_bf16=True)).constraints("masked_pe")
    assert cb.pe_dtype == jnp.bfloat16
    # invalid configs fail even on the local path
    with pytest.raises(ValueError, match="layout"):
        build_executor(LaunchConfig(layout="bogus"))


# -- in-process mesh tests (run under the CI 8-device step; skip otherwise) --

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >=4 host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@needs_devices
def test_mesh_executor_shardings_and_placement():
    import numpy as np
    ex = MeshExecutor(LaunchConfig(mesh="test"))
    assert ex.describe() == {"executor": "mesh",
                             "mesh": {"data": 2, "model": 2}, "layout": "dp"}
    batch = {"tokens": np.zeros((8, 4), np.int32)}
    placed = ex.place_batch(batch)
    assert placed["tokens"].sharding == ex.batch_sharding(8)
    mask = ex.place_mask(np.ones(8, np.float32))
    assert mask.sharding == ex.batch_sharding(8)
    # dp layout: no grad pins, replicated state
    c = ex.constraints("masked_pe")
    assert c.grad is None and c.pe_grad is None
    c2d = MeshExecutor(LaunchConfig(mesh="test", layout="2d")).constraints(
        "masked_pe")
    assert c2d.grad is not None and c2d.pe_grad is not None


# -- subprocess tests (own device count) ------------------------------------

from conftest import run_multidevice_sub as _run_sub  # noqa: E402


@pytest.mark.slow
def test_mesh_fit_matches_local_fit():
    """The acceptance criterion: from_config(..., launch=LaunchConfig(
    mesh="test")) runs fit() sharded on a 2x2 CPU host-device mesh and
    matches the unsharded session — eps bit-identical, params to
    reduction-order ULPs (see module docstring), identical history schema."""
    out = _run_sub(r"""
import jax, json
import jax.numpy as jnp
import numpy as np
from repro.core import DPConfig, LaunchConfig, PrivacySession, TrainConfig

dp = DPConfig(clip_norm=0.1, noise_multiplier=0.7, engine="masked_pe")
tc = TrainConfig(steps=2, n_data=16, q=0.25, seq_len=8, physical_batch=4,
                 seed=0, lr=0.1, optimizer="sgd", momentum=0.0)
local = PrivacySession.from_config("qwen2-0.5b", dp, tc)
out_l = local.fit()
mesh = PrivacySession.from_config("qwen2-0.5b", dp, tc,
                                  launch=LaunchConfig(mesh="test"))
out_m = mesh.fit()
md = max(float(jnp.abs(a - b).max()) for a, b in
         zip(jax.tree.leaves(local.params), jax.tree.leaves(mesh.params)))
# dp_sp keeps the same replicated-state parity contract (the flat grad
# accumulator must NOT be offset-range-sharded here — see
# MeshExecutor.constraints; XLA:CPU SPMD breaks values on that reshard)
sp = PrivacySession.from_config("qwen2-0.5b", dp, tc,
                                launch=LaunchConfig(mesh="test",
                                                    layout="dp_sp"))
sp.fit()
md_sp = max(float(jnp.abs(a - b).max()) for a, b in
            zip(jax.tree.leaves(local.params), jax.tree.leaves(sp.params)))
print(json.dumps({
    "max_param_diff": md,
    "max_param_diff_dp_sp": md_sp,
    "eps_equal": bool(out_l["final_eps"] == out_m["final_eps"]),
    "eps": float(out_m["final_eps"]),
    "hist_keys_equal": [sorted(r) for r in out_l["history"]] ==
                       [sorted(r) for r in out_m["history"]],
    "loss_close": bool(all(abs(a["loss"] - b["loss"]) < 1e-3 for a, b in
                           zip(out_l["history"], out_m["history"]))),
    "mesh_launch": mesh.describe()["launch"],
}))
""")
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["eps_equal"], rec
    assert rec["eps"] > 0
    assert rec["max_param_diff"] < 1e-6, rec     # reduction-order ULPs only
    assert rec["max_param_diff_dp_sp"] < 1e-6, rec
    assert rec["hist_keys_equal"] and rec["loss_close"], rec
    assert rec["mesh_launch"] == {"executor": "mesh",
                                  "mesh": {"data": 2, "model": 2},
                                  "layout": "dp"}


@pytest.mark.slow
def test_mesh_fit_streaming_engine_matches_local():
    """The streaming engine under the mesh: fit() on the 2x2 test mesh in
    BOTH data layouts (dp, dp_sp) matches the unsharded streaming session —
    eps bit-identical, params to reduction-order ULPs.  The scanned tile is
    pinned to the batch axes via ShardingConstraints.tile_batch, so each
    scan iteration's vmapped backward runs data-parallel; the flat
    accumulator stays replicated (see MeshExecutor.constraints)."""
    out = _run_sub(r"""
import jax, json
import jax.numpy as jnp
from repro.core import DPConfig, LaunchConfig, PrivacySession, TrainConfig

dp = DPConfig(clip_norm=0.1, noise_multiplier=0.7,
              engine="masked_fused_stream", stream_tile=2)
tc = TrainConfig(steps=2, n_data=16, q=0.25, seq_len=8, physical_batch=4,
                 seed=0, lr=0.1, optimizer="sgd", momentum=0.0)
local = PrivacySession.from_config("qwen2-0.5b", dp, tc)
out_l = local.fit()
rec = {"eps": float(out_l["final_eps"])}
for layout in ("dp", "dp_sp"):
    mesh = PrivacySession.from_config(
        "qwen2-0.5b", dp, tc,
        launch=LaunchConfig(mesh="test", layout=layout))
    out_m = mesh.fit()
    rec[layout] = {
        "eps_equal": bool(out_l["final_eps"] == out_m["final_eps"]),
        "max_param_diff": max(
            float(jnp.abs(a - b).max()) for a, b in
            zip(jax.tree.leaves(local.params),
                jax.tree.leaves(mesh.params))),
    }
print(json.dumps(rec))
""")
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["eps"] > 0
    for layout in ("dp", "dp_sp"):
        assert rec[layout]["eps_equal"], rec
        assert rec[layout]["max_param_diff"] < 1e-6, rec


@pytest.mark.slow
def test_mesh_generate_runs_sharded():
    out = _run_sub(r"""
import json
from repro.launch.serve import generate
out = generate("qwen2-0.5b", batch=4, prompt_len=4, new_tokens=4,
               mesh="test")
print(json.dumps({"n": len(out["generated"]),
                  "t": len(out["generated"][0])}))
""")
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["n"] == 4 and rec["t"] == 4


LOWER_KEYS = {"arch", "shape", "kind", "mesh", "engine", "microbatches",
              "sampler", "unrolled", "lower_s"}
COMPILE_KEYS = LOWER_KEYS | {"compile_s", "memory", "hlo_cost", "collectives",
                             "analytic", "roofline", "fits_hbm"}
MEMORY_KEYS = {"argument_bytes", "output_bytes", "temp_bytes", "alias_bytes",
               "per_device_total"}
ROOFLINE_KEYS = {"t_compute", "t_memory", "t_collective",
                 "t_collective_analytic", "useful_ratio", "dominant"}


@pytest.mark.slow
def test_dryrun_record_schema_unchanged():
    """dryrun now lowers through MeshExecutor; the JSON records must keep
    their schema (the roofline report consumes them)."""
    out = _run_sub(r"""
import json
from repro.configs.base import SHAPES, InputShape
from repro.launch.dryrun import lower_one

rec1 = lower_one("qwen2-0.5b", "train_4k", mesh="test", smoke=True,
                 compile_=False)
SHAPES["train_tiny"] = InputShape("train_tiny", 16, 8, "train")
rec2 = lower_one("qwen2-0.5b", "train_tiny", mesh="test", smoke=True,
                 microbatches=1, compile_=True)
print(json.dumps({"lower_keys": sorted(rec1),
                  "compile_keys": sorted(rec2),
                  "memory_keys": sorted(rec2["memory"]),
                  "roofline_keys": sorted(rec2["roofline"]),
                  "mesh": rec1["mesh"]}))
""")
    rec = json.loads(out.strip().splitlines()[-1])
    assert set(rec["lower_keys"]) == LOWER_KEYS
    assert set(rec["compile_keys"]) == COMPILE_KEYS
    assert set(rec["memory_keys"]) == MEMORY_KEYS
    assert set(rec["roofline_keys"]) == ROOFLINE_KEYS
    assert rec["mesh"] == {"data": 2, "model": 2}
