"""Pallas kernels vs pure-jnp oracles, swept over shapes and dtypes
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (clip_accum, ghost_norm_dense, noisy_sgd_update,
                           tree_clip_accum, tree_noisy_update)
from repro.kernels import ops
from repro.kernels import ref


@pytest.mark.parametrize("B,D", [(1, 64), (4, 1000), (7, 4096), (16, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_clip_accum_sweep(B, D, dtype):
    k = jax.random.PRNGKey(B * 1000 + D)
    g = jax.random.normal(k, (B, D), dtype).astype(jnp.float32)
    norms = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (B,))) * 2
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (B,)) > 0.3).astype(
        jnp.float32)
    out = clip_accum(g, norms, mask, 0.7, interpret=True, tile_d=256)
    expect = ref.clip_accum_ref(g, norms, mask, 0.7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("B,T,di,do", [(1, 16, 32, 32), (3, 100, 48, 96),
                                       (2, 64, 130, 70), (5, 33, 17, 250)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ghost_norm_sweep(B, T, di, do, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (B, T, di), dtype)
    dy = jax.random.normal(jax.random.PRNGKey(1), (B, T, do), dtype) * 0.1
    out = ghost_norm_dense(x, dy, interpret=True, tiles=(32, 32, 16))
    expect = ref.ghost_norm_dense_ref(x, dy)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=5e-3 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("D", [100, 4096, 10000])
@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_noisy_update_sweep(D, momentum):
    ks = jax.random.split(jax.random.PRNGKey(D), 4)
    p = jax.random.normal(ks[0], (D,))
    a = jax.random.normal(ks[1], (D,))
    z = jax.random.normal(ks[2], (D,))
    if momentum:
        m = jax.random.normal(ks[3], (D,))
        newp, newm = noisy_sgd_update(p, a, z, 1.5, 64.0, 0.01,
                                      momentum_buf=m, momentum=momentum,
                                      interpret=True, tile=512)
        rp, rm = ref.noisy_sgd_update_ref(p, a, z, 1.5, 64.0, 0.01, m, momentum)
        np.testing.assert_allclose(np.asarray(newm), np.asarray(rm),
                                   rtol=1e-5, atol=1e-6)
    else:
        newp = noisy_sgd_update(p, a, z, 1.5, 64.0, 0.01, interpret=True,
                                tile=512)
        rp = ref.noisy_sgd_update_ref(p, a, z, 1.5, 64.0, 0.01)
    np.testing.assert_allclose(np.asarray(newp), np.asarray(rp),
                               rtol=1e-5, atol=1e-6)


def test_tree_wrappers_match_engine():
    """tree_clip_accum == the pe engine's clip+sum on a real grads pytree."""
    B = 5
    grads = {"a": {"w": jax.random.normal(jax.random.PRNGKey(0), (B, 8, 16))},
             "b": jax.random.normal(jax.random.PRNGKey(1), (B, 33))}
    sq = sum(jnp.sum(g.reshape(B, -1) ** 2, -1) for g in jax.tree.leaves(grads))
    norms = jnp.sqrt(sq)
    mask = jnp.array([1., 0., 1., 1., 0.])
    out = tree_clip_accum(grads, norms, mask, 0.3, interpret=True)

    from repro.core.clipping import clip_coef
    coef, _ = clip_coef(sq, mask, 0.3)
    for path in ("a", "b"):
        g = grads[path]["w"] if path == "a" else grads[path]
        o = out[path]["w"] if path == "a" else out[path]
        expect = jnp.sum(g * coef.reshape((-1,) + (1,) * (g.ndim - 1)), 0)
        np.testing.assert_allclose(np.asarray(o), np.asarray(expect),
                                   rtol=1e-5, atol=1e-6)


def test_tree_noisy_update_roundtrip():
    params = {"w": jnp.ones((10, 3)), "b": jnp.zeros((7,))}
    acc = jax.tree.map(jnp.ones_like, params)   # legacy pytree accumulator
    new, mom = tree_noisy_update(params, acc, jax.random.PRNGKey(0),
                                 0.0, 2.0, 0.5)
    assert mom is None
    np.testing.assert_allclose(np.asarray(new["w"]),
                               np.ones((10, 3)) - 0.5 * 0.5, rtol=1e-6)


def test_tree_noisy_update_kernel_matches_xla():
    """The Pallas path (interpret mode, per-leaf segments of the flat
    accumulator) and the pure-XLA flat-fused expression are the same math —
    including momentum, noise, and the non-private seen-count divide."""
    from repro.utils.params import FlatGradView
    params = {"a": {"w": jax.random.normal(jax.random.PRNGKey(0), (9, 5))},
              "b": jax.random.normal(jax.random.PRNGKey(1), (33,))}
    view = FlatGradView.for_tree(params)
    acc = jax.random.normal(jax.random.PRNGKey(2), (view.total,))
    mom = jax.random.normal(jax.random.PRNGKey(3), (view.total,))
    key = jax.random.PRNGKey(4)
    for m in (None, mom):
        px, mx = tree_noisy_update(params, acc, key, 1.3, 16.0, 0.05,
                                   momentum_buf=m, momentum=0.9, view=view,
                                   use_kernel=False)
        pk, mk = tree_noisy_update(params, acc, key, 1.3, 16.0, 0.05,
                                   momentum_buf=m, momentum=0.9, view=view,
                                   use_kernel=True, interpret=True)
        for a, b in zip(jax.tree.leaves(px), jax.tree.leaves(pk)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        if m is not None:
            np.testing.assert_allclose(np.asarray(mx[:view.n_params]),
                                       np.asarray(mk[:view.n_params]),
                                       rtol=1e-5, atol=1e-6)
    # non-private: no key, traced seen-count denominator
    px, _ = tree_noisy_update(params, acc, None, 0.0, jnp.float32(3.0), 0.1,
                              view=view, use_kernel=False)
    pk, _ = tree_noisy_update(params, acc, None, 0.0, jnp.float32(3.0), 0.1,
                              view=view, use_kernel=True, interpret=True)
    for a, b in zip(jax.tree.leaves(px), jax.tree.leaves(pk)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def _inplace_case(m, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    acc = jax.random.normal(ks[0], (D,))
    g = jax.random.normal(ks[1], (m, D))
    norms = jnp.abs(jax.random.normal(ks[2], (m,))) * 2
    mask = (jax.random.uniform(ks[3], (m,)) > 0.3).astype(jnp.float32)
    return acc, g, norms, mask


@pytest.mark.parametrize("m", [1, 2, 3, 8])
def test_clip_accum_inplace_matches_ref(m):
    """Aliased streaming kernel vs the strict-fold oracle — BITWISE, with a
    multi-program grid (D=512, tile_d=256): the kernel's canonical reduction
    order is the whole point, allclose would not pin it."""
    from repro.kernels.clip_accum import clip_accum_inplace
    acc, g, norms, mask = _inplace_case(m, 512)
    out = clip_accum_inplace(acc, g, norms, mask, 0.7, interpret=True,
                             tile_d=256)
    expect = ref.clip_accum_inplace_ref(acc, g, norms, mask, 0.7)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_clip_accum_inplace_tile_invariance():
    """One m=4 call == two m=2 calls == four m=1 calls, bitwise: the kernel
    folds FROM the carried accumulator, so any tiling of the example axis is
    the same long strict fold.  m=1 specifically exercises the opaque
    trip-count (a constant-unrolled length-1 fold would FMA-contract and
    break this)."""
    from repro.kernels.clip_accum import clip_accum_inplace
    acc, g, norms, mask = _inplace_case(4, 256, seed=3)
    whole = clip_accum_inplace(acc, g, norms, mask, 0.5, interpret=True)
    two = acc
    for i in (0, 2):
        two = clip_accum_inplace(two, g[i:i + 2], norms[i:i + 2],
                                 mask[i:i + 2], 0.5, interpret=True)
    ones = acc
    for i in range(4):
        ones = clip_accum_inplace(ones, g[i:i + 1], norms[i:i + 1],
                                  mask[i:i + 1], 0.5, interpret=True)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(two))
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(ones))


def test_clip_accum_inplace_padded_tail_stays_zero():
    """FlatGradView accumulators carry an alignment tail past n_params.  The
    streaming tile is zero over that tail, so accumulating must leave the
    tail EXACTLY zero — any epsilon there would leak into the momentum
    buffer's tail segment."""
    from repro.kernels.clip_accum import clip_accum_inplace
    D, n_params = 512, 456
    acc = jnp.zeros((D,))
    for seed in (0, 1):
        _, g, norms, mask = _inplace_case(3, D, seed=seed)
        g = g.at[:, n_params:].set(0.0)
        acc = clip_accum_inplace(acc, g, norms, mask, 0.9, interpret=True)
    out = np.asarray(acc)
    assert np.all(out[n_params:] == 0.0)
    assert np.any(out[:n_params] != 0.0)


def test_clip_accum_inplace_shape_errors():
    from repro.kernels.clip_accum import clip_accum_inplace
    acc, g, norms, mask = _inplace_case(2, 300)
    with pytest.raises(ValueError, match="must divide"):
        clip_accum_inplace(acc, g, norms, mask, 1.0, interpret=True,
                           tile_d=256)
    with pytest.raises(ValueError, match="acc shape"):
        clip_accum_inplace(acc[:256], g, norms, mask, 1.0, interpret=True)


def _tf_stream(seed, total):
    """The in-kernel interpret-mode noise stream, recomputed outside the
    kernel: counter = global flat element index, c1 = 0."""
    from repro.kernels import threefry2x32, bits_to_normal
    b1, b2 = threefry2x32(seed[0], seed[1],
                          jnp.arange(total, dtype=jnp.uint32),
                          jnp.zeros((total,), jnp.uint32))
    return bits_to_normal(b1, b2)


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_noisy_update_in_kernel_threefry_parity(momentum):
    """seed= (in-kernel threefry draw) vs noise= (the same stream computed
    outside and fed as the flat operand): bitwise-identical parameters.
    D=1000 with tile=512 makes the grid multi-program, so this also pins the
    counter being the GLOBAL element index, not a per-tile restart."""
    D, tile = 1000, 512
    ks = jax.random.split(jax.random.PRNGKey(D), 4)
    p = jax.random.normal(ks[0], (D,))
    a = jax.random.normal(ks[1], (D,))
    seed = jnp.array([1234, 5678], jnp.uint32)
    z = _tf_stream(seed, D + (-D) % tile)[:D]
    kw = {}
    if momentum:
        kw = dict(momentum_buf=jax.random.normal(ks[2], (D,)),
                  momentum=momentum)
    got = noisy_sgd_update(p, a, None, 1.5, 64.0, 0.01, seed=seed,
                           interpret=True, tile=tile, **kw)
    want = noisy_sgd_update(p, a, z, 1.5, 64.0, 0.01, interpret=True,
                            tile=tile, **kw)
    got = got if momentum else (got,)
    want = want if momentum else (want,)
    for gw, ww in zip(got, want):
        np.testing.assert_array_equal(np.asarray(gw), np.asarray(ww))


def test_tree_noisy_update_in_kernel_rng_reproducible():
    """Tree-level in_kernel_rng=True on the interpret path: every leaf's
    update is reproducible outside the kernel from (key, leaf index) via the
    documented counter scheme — and leaves get distinct streams."""
    from repro.kernels.noisy_update import TILE
    from repro.utils.params import FlatGradView
    params = {"a": {"w": jax.random.normal(jax.random.PRNGKey(0), (9, 5))},
              "b": jax.random.normal(jax.random.PRNGKey(1), (33,))}
    view = FlatGradView.for_tree(params)
    acc = jax.random.normal(jax.random.PRNGKey(2), (view.total,))
    key = jax.random.PRNGKey(7)
    newp, _ = ops.tree_noisy_update(params, acc, key, 1.3, 16.0, 0.05,
                                    view=view, use_kernel=True,
                                    interpret=True, in_kernel_rng=True)
    kd = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)[-2:]
    zs = []
    for i, (p, got) in enumerate(zip(jax.tree.leaves(params),
                                     jax.tree.leaves(newp))):
        o, n = view.offsets[i], view.sizes[i]
        z = _tf_stream(kd + jnp.uint32(i), n + (-n) % TILE)[:n]
        zs.append(np.asarray(z))
        expect = noisy_sgd_update(p.reshape(-1), acc[o:o + n], z,
                                  1.3, 16.0, 0.05, interpret=True)
        np.testing.assert_array_equal(np.asarray(got).reshape(-1),
                                      np.asarray(expect))
    assert not np.array_equal(zs[0][:33], zs[1])


def test_bits_to_normal_is_standard_normal():
    """The Box–Muller transform behind the in-kernel TPU noise path (the
    kernel itself needs pltpu.prng_*, which has no interpret lowering):
    uniform uint32 bits in, N(0,1) out — checked on moments and finiteness."""
    from repro.kernels import bits_to_normal
    rng = np.random.default_rng(0)
    n = 200_000
    b1 = jnp.asarray(rng.integers(0, 2 ** 32, size=n, dtype=np.uint32))
    b2 = jnp.asarray(rng.integers(0, 2 ** 32, size=n, dtype=np.uint32))
    z = np.asarray(bits_to_normal(b1, b2))
    assert np.all(np.isfinite(z))
    assert abs(z.mean()) < 0.01
    assert abs(z.std() - 1.0) < 0.01
    # extreme bits stay finite (u1=0 would be -inf; the offset prevents it)
    z0 = np.asarray(bits_to_normal(jnp.zeros(4, jnp.uint32),
                                   jnp.zeros(4, jnp.uint32)))
    assert np.all(np.isfinite(z0))
