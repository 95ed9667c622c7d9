"""Cross-validate the analytic roofline cost model against exact HLO flop
counts from a fully-unrolled single-device compile (no scan undercount)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig, InputShape
from repro.core import DPConfig, Tape, build_fused_step, init_state
from repro.core.tape import set_scan_unroll
from repro.launch import costmodel
from repro.models import build
from repro.optim import sgd


@pytest.fixture
def small_cfg():
    return ArchConfig(name="t", family="dense", n_layers=4, d_model=256,
                      n_heads=4, n_kv_heads=4, d_ff=512, vocab=512,
                      dtype="float32")


def _hlo_flops(model, cfg, shape, engine):
    set_scan_unroll(cfg.n_layers)
    try:
        dpc = DPConfig(1.0, 1.0, float(shape.global_batch), engine, 1)
        opt = sgd(1e-3)
        step = build_fused_step(lambda p, b, t: model.loss(p, b, t), opt, dpc)
        state_shape = jax.eval_shape(
            lambda: init_state(model.init(jax.random.PRNGKey(0)), opt,
                               jax.random.PRNGKey(1)))
        batch = {"tokens": jax.ShapeDtypeStruct(
                    (shape.global_batch, shape.seq_len), jnp.int32),
                 "labels": jax.ShapeDtypeStruct(
                    (shape.global_batch, shape.seq_len), jnp.int32)}
        mask = jax.ShapeDtypeStruct((shape.global_batch,), jnp.float32)
        c = jax.jit(step).lower(state_shape, batch, mask).compile()
        return (c.cost_analysis() or {}).get("flops", 0.0)
    finally:
        set_scan_unroll(1)


@pytest.mark.slow
@pytest.mark.parametrize("engine", ["nonprivate", "masked_ghost", "masked_bk"])
def test_analytic_flops_within_band(small_cfg, engine):
    """Analytic model within a 2x band of exact unrolled HLO flops (the HLO
    includes softmax/norm/noise pointwise work the model ignores; the model
    includes MXU-shaped matmul counts the HLO may fuse)."""
    cfg = small_cfg
    model = build(cfg)
    shape = InputShape("t", 64, 8, "train")
    hlo = _hlo_flops(model, cfg, shape, engine)
    ana = costmodel.train_costs(model, cfg, shape, engine, {"data": 1}).flops
    assert ana > 0 and hlo > 0
    ratio = ana / hlo
    assert 0.5 < ratio < 2.0, f"analytic/hlo = {ratio}"


def test_param_stats_exact(small_cfg):
    model = build(small_cfg)
    n, n_active, flat = costmodel.param_stats(model, small_cfg)
    params = model.init(jax.random.PRNGKey(0))
    exact = sum(x.size for x in jax.tree.leaves(params))
    assert n == exact
    assert n_active == exact  # dense: no discount


def test_moe_active_discount():
    cfg = ArchConfig(name="m", family="moe", n_layers=2, d_model=64,
                     n_heads=2, n_kv_heads=2, d_ff=128, moe_d_ff=128,
                     vocab=128, n_experts=8, top_k=2)
    model = build(cfg)
    n, n_active, _ = costmodel.param_stats(model, cfg)
    assert n_active < n
    # expert params discounted by 2/8
    expert = 2 * 3 * 8 * 64 * 128  # L * 3 mats * E * d * ff
    assert n - n_active == pytest.approx(expert * (1 - 2 / 8))


def test_decode_costs_scale_with_cache():
    cfg = ArchConfig(name="d", family="dense", n_layers=2, d_model=64,
                     n_heads=2, n_kv_heads=2, d_ff=128, vocab=128)
    model = build(cfg)
    s1 = costmodel.decode_costs(model, cfg, InputShape("a", 1024, 4, "decode"),
                                {"data": 1})
    s2 = costmodel.decode_costs(model, cfg, InputShape("b", 4096, 4, "decode"),
                                {"data": 1})
    assert s2.hbm_bytes > s1.hbm_bytes
    assert s2.detail["cache_bytes"] == pytest.approx(
        4 * s1.detail["cache_bytes"])


@pytest.mark.parametrize("rows,state_rows,want", [
    (40, 0, 32),    # the whole batch fits
    (13, 0, 8),     # cut below the batch: a multiple of the 8-row tiling
    (5, 0, 5),      # fewer than 8 rows fit: as many as fit
    (13, 6, 7),     # the live train state comes off the budget first
    (0, 0, 1),      # nothing left: one row
])
def test_stream_tile_size_budget(rows, state_rows, want):
    """The streaming tile is the largest m whose per-example rows fit the
    budget net of the fixed f32 buffers and the live train state."""
    n = 1_000_000
    row = costmodel.STREAM_ROW_COPIES * 4 * n
    budget = costmodel.STREAM_FIXED_F32_BUFFERS * 4 * n + rows * row
    assert costmodel.stream_tile_size(32, n, budget_bytes=budget,
                                      state_bytes=state_rows * row) == want
