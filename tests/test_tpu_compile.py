"""The main path's Pallas kernels, compiled for a described TPU v5e chip.

Nothing runs: each test lowers a kernel with ``interpret=False`` at the
widths a real model gives it and compiles it with the TPU compiler for a
chip that is described, not attached.  That refuses what interpret mode
accepts (blocks off the (8, 128) tiling, primitives Mosaic cannot lower,
casts the chip lacks), so these tests guard the chip path at no chip time.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.clip_accum import clip_accum, clip_accum_inplace
from repro.kernels.ghost_norm import ghost_norm_dense
from repro.kernels.noisy_update import noisy_sgd_update


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def vit_flat(one_chip):
    """vit-base (full width) parameter count and flat accumulator length."""
    from repro.models import build_by_name
    from repro.utils.params import FlatGradView
    model, _ = build_by_name("vit-base", smoke=False)
    view = FlatGradView.for_tree(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    return view.n_params, view.total


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_ghost_norm_dense_qwen2_width(one_chip):
    # qwen2-0.5b: d_model 896 -> 896 projections at T = 2048
    c = _compile(one_chip,
                 lambda x, dy: ghost_norm_dense(x, dy, interpret=False,
                                                tiles=(128, 128, 128)),
                 ((4, 2048, 896), jnp.float32), ((4, 2048, 896), jnp.float32))
    _assert_kernel(c)


def _clip_accum(one_chip, n, rows=16, dtype=jnp.float32):
    # the resident form over the unpadded parameter count: the D-block the
    # VMEM budget picks for ``rows`` rows, with a partial last block
    return _compile(one_chip,
                    lambda g, nr, m: clip_accum(g, nr, m, 1.0,
                                                interpret=False),
                    ((rows, n), dtype), ((rows,), jnp.float32),
                    ((rows,), jnp.float32))


def _clip_accum_inplace(one_chip, total, m=8, dtype=jnp.float32):
    # the streaming form over the aligned flat length, m-row tile
    return _compile(one_chip,
                    lambda a, g, nr, k: clip_accum_inplace(a, g, nr, k, 1.0,
                                                           interpret=False),
                    ((total,), jnp.float32), ((m, total), dtype),
                    ((m,), jnp.float32), ((m,), jnp.float32))


def test_clip_accum_vit_flat_length(one_chip, vit_flat):
    n, _ = vit_flat
    _assert_kernel(_clip_accum(one_chip, n))


@pytest.mark.parametrize("rows,dtype", [(16, jnp.bfloat16),
                                        (32, jnp.float32)])
def test_clip_accum_vit_flat_length_rows(one_chip, vit_flat, rows, dtype):
    n, _ = vit_flat
    _assert_kernel(_clip_accum(one_chip, n, rows, dtype))


def test_clip_accum_inplace_vit_flat_length(one_chip, vit_flat):
    _, total = vit_flat
    _assert_kernel(_clip_accum_inplace(one_chip, total))


@pytest.mark.parametrize("m,dtype", [(8, jnp.bfloat16), (32, jnp.float32)])
def test_clip_accum_inplace_vit_flat_length_rows(one_chip, vit_flat, m,
                                                 dtype):
    _, total = vit_flat
    _assert_kernel(_clip_accum_inplace(one_chip, total, m, dtype))


@pytest.mark.parametrize("momentum", [False, True])
@pytest.mark.parametrize("noise", ["operand", "in_kernel_rng"])
def test_noisy_sgd_update_vit_flat_length(one_chip, vit_flat, noise,
                                          momentum):
    n, _ = vit_flat
    flat = ((n,), jnp.float32)
    shapes = [flat, flat,
              flat if noise == "operand" else ((2,), jnp.uint32)]
    if momentum:
        shapes.append(flat)

    def fn(p, a, z_or_seed, m=None):
        kw = dict(momentum_buf=m, momentum=0.9) if momentum else {}
        if noise == "operand":
            return noisy_sgd_update(p, a, z_or_seed, 1.0, 32.0, 1e-3,
                                    interpret=False, **kw)
        return noisy_sgd_update(p, a, None, 1.0, 32.0, 1e-3,
                                seed=z_or_seed, interpret=False, **kw)

    _assert_kernel(_compile(one_chip, fn, *shapes))


def test_vit_stream_accumulate_fits_one_chip(one_chip, vit_flat,
                                             monkeypatch):
    """The whole masked_fused_stream accumulate at vit-base width and
    physical batch 16, with the tile the memory budget picks: it carries
    the aliased kernel and fits a v5e's HBM beside the train state."""
    import repro.core.fused
    import repro.core.layers
    import repro.kernels.ops
    from repro.core.engine import DPConfig, build_accumulate_fn, init_state
    from repro.launch.mesh import HBM_BYTES
    from repro.models import build_by_name
    from repro.optim import sgd
    for mod in (repro.core.fused, repro.core.layers, repro.kernels.ops):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    model, _ = build_by_name("vit-base", smoke=False)
    opt = sgd(1e-3, momentum=0.9)
    state = jax.eval_shape(lambda: init_state(
        model.init(jax.random.PRNGKey(0)), opt, jax.random.PRNGKey(1)))
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), state)
    batch = {"image": jax.ShapeDtypeStruct((16, 224, 224, 3), jnp.float32,
                                           sharding=one_chip),
             "label": jax.ShapeDtypeStruct((16,), jnp.int32,
                                           sharding=one_chip)}
    mask = jax.ShapeDtypeStruct((16,), jnp.float32, sharding=one_chip)
    fn = build_accumulate_fn(lambda p, b, t: model.loss(p, b, t),
                             DPConfig(engine="masked_fused_stream",
                                      expected_batch_size=32.0))
    c = jax.jit(fn).lower(state, batch, mask).compile()
    _assert_kernel(c)
    ma = c.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert peak <= HBM_BYTES, peak
