"""Operations and bytes of each kernel call, and the model FLOPs of one
example, against numbers worked out by hand."""
import pytest

from _paths import BENCH  # noqa: F401

import cell
import flops


def _count(kernel, operands, results):
    return cell.load_module("counts", kernel).count(operands, results)


def test_clip_accum_counts():
    # acc (1, 1024) f32 += 16 bf16 rows of 1024
    f, b = _count("clip_accum",
                  [((1, 1024), 4), ((16, 1024), 2), ((16, 1), 4),
                   ((16, 1), 4), ((1,), 4), ((1,), 4)],
                  [((1, 1024), 4)])
    assert f == 2 * 16 * 1024
    assert b == 16 * 1024 * 2 + 2 * 1024 * 4


@pytest.mark.parametrize("momentum", [True, False])
def test_noisy_update_counts(momentum):
    blocks = [((64, 128), 4)] * (3 if momentum else 2)
    ops = [((2,), 4)] + blocks + [((1, 4), 4)]
    res = [((64, 128), 4)] * (2 if momentum else 1)
    f, b = _count("noisy_update", ops, res)
    n = 64 * 128
    assert b == (5 if momentum else 3) * n * 4
    assert f == (7 if momentum else 5) * n


def test_ghost_norm_counts():
    f, b = _count("ghost_norm", [((2, 512, 896), 4), ((2, 512, 128), 4)],
                  [((2, 8, 128), 4)])
    assert f == 2 * 2 * 512 * 896 * 128 + 2 * 2 * 896 * 128
    assert b == 2 * 512 * (896 + 128) * 4 + 2 * 8 * 128 * 4


def _leaves(model):
    import jax
    from repro.configs.base import ArchConfig
    from repro.models import build
    import pools
    shapes = jax.eval_shape(build(ArchConfig(**model)).init,
                            jax.random.PRNGKey(0))
    return dict(zip(pools.leaf_paths(shapes),
                    [x.shape for x in jax.tree.leaves(shapes)]))


def test_model_flops_vit_base():
    model = cell.load_spec("vit-base.dp-stream.poisson-1024")["config"][
        "model"]
    T, D, F, L = 197, 768, 3072, 12
    per_layer = 2 * T * (4 * D * D + 2 * D * F)
    fwd = (L * per_layer + 2 * 196 * 768 * D + 2 * 1 * D * 100
           + 4 * L * T * T * 12 * 64)
    assert flops.per_example(model, _leaves(model), 0) == 3 * fwd


def test_model_flops_qwen2():
    import json
    import os
    with open(os.path.join(BENCH, "configs", "qwen2-0.5b.json")) as f:
        model = json.load(f)["model"]
    T, D, F, L, V = 512, 896, 4864, 24, 151936
    per_layer = 2 * T * (2 * D * D + 2 * D * 128 + 3 * D * F)
    fwd = L * per_layer + 2 * T * D * V + 4 * L * T * T * 14 * 64
    assert flops.per_example(model, _leaves(model), 512) == 3 * fwd
