"""The reference's sampler and accountant against the program's, and the
comparison's arithmetic on hand-made numbers."""
import numpy as np
import pytest

from _paths import BENCH  # noqa: F401

import check
from reference import rdp, sampler_poisson


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_poisson_draw_matches_program(seed):
    from repro.data import make_sampler
    s = make_sampler("poisson", n=5000, q=0.02, seed=seed)
    for k in (0, 1, 17):
        assert np.array_equal(s.at_step(k),
                              sampler_poisson.draw(seed, k, 5000, 0.02))


@pytest.mark.parametrize("q,sigma,steps", [(0.02048, 0.9, 3),
                                           (0.00256, 0.6, 1),
                                           (0.08192, 1.3, 125)])
def test_rdp_matches_program_accountant(q, sigma, steps):
    from repro.privacy import rdp as prog
    delta = 1e-6
    want = prog.epsilon(q, sigma, steps, delta)
    assert rdp.epsilon(q, sigma, steps, delta) == pytest.approx(want,
                                                                rel=1e-12)
    # the control's float32 arithmetic is measurably off
    e32 = rdp.epsilon(q, sigma, steps, delta, np.float32)
    assert e32 != want


def test_sample_mismatch():
    # the fetched index arrays in fetch order, over all steps
    drawn = [np.array([3, 5, 9]), np.array([1, 2, 4, 6, 8])]
    ok = [np.array([3, 5, 9, 0]), np.array([1, 2, 4, 6]),
          np.array([8, 0, 0, 0])]
    assert check.sample_mismatch(ok, drawn, 4) == 0
    wrong = [np.array([3, 5, 7, 0]), np.array([1, 2, 4, 6])]
    # one index differs; the second step lost a whole physical batch
    assert check.sample_mismatch(wrong, drawn, 4) == 1 + 4 + 1
    # a batch dropped inside the first step shifts every later one
    dropped = [np.array([1, 2, 4, 6]), np.array([8, 0, 0, 0])]
    assert check.sample_mismatch(dropped, drawn, 4) == 3 + 1 + 4 + 4 + 1
    # a batch past the last step counts whole
    assert check.sample_mismatch(ok + [np.array([7, 0, 0, 0])], drawn,
                                 4) == 4


def test_noise_stats():
    rng = np.random.default_rng(3)
    a, b = rng.standard_normal(100_000), rng.standard_normal(100_000)
    std_err, corr = check.noise_stats([2 * a, 2 * b], 2.0, 100_000)
    assert std_err < 0.01 and corr < 0.02
    # the same key twice: the same noise
    assert check.noise_stats([a, b, b], 1.0, 100_000)[1] == pytest.approx(
        1.0, abs=1e-4)


def test_leaf_gaps():
    # the near-zero leaf is left out; gaps over max(own norm, median)
    ref = np.array([2.0, 4.0, 2e-6])
    prog = np.array([2.2, 4.0, 10.0])
    gaps = check.leaf_gaps(prog, ref)
    assert gaps[0] == pytest.approx(0.2 / 2.0) and gaps[1] == 0.0
    assert np.isnan(gaps[2])
    assert check.leaf_gaps(np.array([np.nan, 4.0, 1.0]), ref)[0] == np.inf


def test_compare_cosine():
    import jax.numpy as jnp
    ref = [jnp.array([1.0, 0.0]), jnp.array([0.0, 2.0])]
    same = check.compare(ref, lambda i: ref[i])
    assert same[1] == pytest.approx(0.0, abs=1e-7)
    flipped = check.compare(ref, lambda i: -ref[i])
    assert np.all(flipped[0] == 0.0) and flipped[1] == pytest.approx(2.0)


@pytest.mark.parametrize("kind", ["images", "tokens"])
def test_pool_from_seed(kind):
    import pools
    model = {"image_size": 8, "n_classes": 10, "vocab": 97}
    a = pools.make_pool({"kind": kind, "size": 16}, model, 4, 100, 2**33 + 1)
    b = pools.make_pool({"kind": kind, "size": 16}, model, 4, 100, 2**33 + 1)
    c = pools.make_pool({"kind": kind, "size": 16}, model, 4, 100, 2**33 + 2)
    idx = np.array([3, 17, 99])
    for k in a.arrays:
        assert np.array_equal(a.fetch(idx)[k], b.fetch(idx)[k])
        assert np.array_equal(a.fetch(idx)[k], a.arrays[k][idx % 16])
    assert not all(np.array_equal(a.arrays[k], c.arrays[k]) for k in a.arrays)


def test_weights_from_seed():
    import jax
    import pools
    shapes = {"blocks": {"ln1": {"w": jax.ShapeDtypeStruct((2, 8), "f4")},
                         "mlp": {"w1": {"w": jax.ShapeDtypeStruct(
                             (2, 8, 16), "f4")}}},
              "emb": {"w": jax.ShapeDtypeStruct((5, 8), "f4")}}
    init = [["ln1\\.w$", "ones", 0], ["^emb\\.w$", "normal", 0.02],
            ["\\.w$", "fan_in", 0]]
    a = pools.make_weights(shapes, init, 2**31 + 9)
    b = pools.make_weights(shapes, init, 2**31 + 9)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert np.all(np.asarray(a["blocks"]["ln1"]["w"]) == 1)
    w1 = np.asarray(a["blocks"]["mlp"]["w1"]["w"])
    assert 0.2 < w1.std() * 8 ** 0.5 < 5
