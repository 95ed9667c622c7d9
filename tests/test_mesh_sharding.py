"""Sharding rules + small-mesh lower/compile tests.

jax locks the device count on first init, so the multi-device cases run in a
subprocess with xla_force_host_platform_device_count set (the same discipline
as launch/dryrun.py — and why that env var must NOT be global).
"""
import json

import pytest

from jax.sharding import PartitionSpec as P

from conftest import run_multidevice_sub as _run_sub


class FakeMesh:
    shape = {"data": 4, "model": 2}


def test_param_pspec_rules():
    from repro.utils.sharding import param_pspec
    m = FakeMesh()
    # up-proj: dout on model, din on data
    assert param_pspec(("blocks", "attn", "wq", "w"), (8, 16), m) == \
        P("data", "model")
    # down-proj: din on model
    assert param_pspec(("blocks", "attn", "wo", "w"), (8, 16), m) == \
        P("model", "data")
    # stacked: leading layer dim unsharded
    assert param_pspec(("blocks", "mlp", "w1", "w"), (3, 8, 16), m) == \
        P(None, "data", "model")
    # vectors replicated
    assert param_pspec(("blocks", "ln1", "w"), (16,), m) == P()
    # non-divisible dims stay replicated
    assert param_pspec(("x", "wq", "w"), (7, 9), m) == P()
    # experts on model
    assert param_pspec(("blocks", "moe", "w1", "w"), (4, 8, 16), m) == \
        P("model", "data", None)
    # embedding vocab-parallel
    assert param_pspec(("emb", "w"), (100, 8), m) == P("model", "data")


@pytest.mark.slow
def test_small_mesh_train_lowering():
    out = _run_sub(r"""
import jax, json, dataclasses
from repro.configs.base import InputShape, input_specs
from repro.core import DPConfig, build_fused_step, init_state
from repro.launch.executor import LaunchConfig, MeshExecutor
from repro.models import build, build_by_name
from repro.optim import sgd

ex = MeshExecutor(LaunchConfig(mesh=(4, 2), axes=("data", "model"),
                               layout="2d"))
model, cfg = build_by_name("qwen3-1.7b", smoke=True)
cfg = dataclasses.replace(cfg, vocab=96, d_model=128)
model = build(cfg)
dpc = DPConfig(1.0, 1.0, 8.0, "masked_ghost", 2)
opt = sgd(1e-3)
step = build_fused_step(lambda p,b,t: model.loss(p,b,t), opt, dpc,
                        constraints=ex.constraints("masked_ghost"))
state_shape = jax.eval_shape(lambda: init_state(model.init(jax.random.PRNGKey(0)), opt, jax.random.PRNGKey(1)))
specs = input_specs(cfg, InputShape("t", 16, 8, "train"))
c = ex.lower_train(step, state_shape, specs["batch"], specs["mask"]).compile()
ma = c.memory_analysis()
ca = c.cost_analysis() or {}
print(json.dumps({"ok": True, "temp": ma.temp_size_in_bytes,
                  "flops": ca.get("flops", -1)}))
""")
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["ok"]


@pytest.mark.slow
def test_small_mesh_decode_lowering():
    out = _run_sub(r"""
import jax, jax.numpy as jnp, json
from repro.launch.executor import LaunchConfig, MeshExecutor
from repro.models import build_by_name

ex = MeshExecutor(LaunchConfig(mesh=(4, 2), axes=("data", "model"),
                               layout="2d"))
model, cfg = build_by_name("mamba2-1.3b", smoke=True)
params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
cache_shape = jax.eval_shape(lambda p: model.init_cache(p, 8, 32), params_shape)
tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
pos = jax.ShapeDtypeStruct((), jnp.int32)
c = ex.lower_decode(model.decode_step, params_shape, cache_shape,
                    tok, pos).compile()
print(json.dumps({"ok": True}))
""")
    assert json.loads(out.strip().splitlines()[-1])["ok"]


@pytest.mark.slow
def test_multipod_mesh_axes():
    out = _run_sub(r"""
import jax, json
# 8 host devices: use a (2,2,2) stand-in with the production axis names
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
print(json.dumps({"axes": list(mesh.shape.keys()),
                  "n": len(mesh.devices.ravel().tolist())}))
""")
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["axes"] == ["pod", "data", "model"] and rec["n"] == 8
