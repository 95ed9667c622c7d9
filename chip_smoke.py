#!/usr/bin/env python3
"""Run the DP-SGD trainer on TPU and check what comes out.

One process, no subprocesses.  The model is vit-base at its full published
width (224x224 images, patch 16, 12 layers of width 768, 100 classes) with
random weights made from a seed; only the number of steps is cut.

    python chip_smoke.py             # one chip: phases A, B and C
    python chip_smoke.py --chips 4   # four chips: data-parallel fit against
                                     # the same fit on one chip, nothing else

Phase A  trains 3 DP-SGD steps through the normal entry point
         (``repro.launch.train.make_session`` + ``fit``) with each of
         ``masked_fused_stream`` and ``masked_ghost``: Poisson sampling
         (n=1024, q=1/32), physical batch 16, SGD with momentum, sigma
         calibrated to eps=8.  Checks finite losses, eps equal to the
         accountant's expected trajectory, and Pallas kernels
         (``tpu_custom_call``) in the compiled accumulate and update.
Phase B  compares on the chip against the plain paths: the streaming
         engine's accumulator against ``masked_pe``'s (at "highest" matmul
         precision to f32 rounding; at the default within the default's
         own error), the update kernel (noise operand) against the XLA
         update on one key, and the in-kernel noise over the whole flat
         buffer.
Phase C  runs the static privacy verifier on the step the chip traces.

Every check prints its value beside its bound.  The last line of standard
output is one JSON object, ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``, printed only when every check passed.  With no
TPU, or with no ``src/repro`` beside this file, it exits nonzero and prints
no such line.  The compile cache follows ``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "vit-base"
ENGINES = ("masked_fused_stream", "masked_ghost")
# the training run every phase shares: make_session's own arguments
RUN = dict(smoke=False, steps=3, n_data=1024, physical=16, q=1 / 32,
           sampler="poisson", target_eps=8.0, optimizer="sgd", seed=0)

# Phase B / four-chip bounds (see CHANGES.md for why each is what it is)
PB_WIDTH = 8            # per-example width both Phase B engines run at
DP4_TILE = 8            # four-chip streaming tile: 2 rows on each chip
ACC_REL_L2 = 1e-5       # accumulators at "highest" matmul precision, rel L2
ACC_DEFAULT_MAX = 5e-2  # masked_pe at default precision vs at highest
UPDATE_REL_ELEM = 2.0 ** -18  # update kernel vs XLA: new params, elementwise
UPDATE_REL_L2 = 1e-5    # ... and new momentum, relative L2
SIGMAS = 5.0            # noise statistics: |value - expected| <= 5 std errors
DP4_ACC_REL_L2 = 1e-3   # 4-chip vs 1-chip accumulator at "highest", rel L2
DP4_REL_L2 = 1e-2       # 4-chip vs 1-chip params, relative to the update


class Checks:
    """Prints each check beside its bound and remembers failures."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, value, bound: str, ok: bool) -> bool:
        print(f"  {'PASS' if ok else 'FAIL'}  {name} = {value}  ({bound})",
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def phase(title: str, check: Checks, fn, *args) -> None:
    print(f"== {title}", flush=True)
    try:
        fn(check, *args)
    except Exception:
        traceback.print_exc()
        check(f"{title} ran", "raised", "must not raise", False)
    gc.collect()            # drop the phase's device buffers before the next


def first_batch(session):
    """The first RUN['physical'] examples, with the last three masked out
    (padding rows must contribute exactly nothing)."""
    import numpy as np
    from repro.data.synthetic import dataset_for_config
    tc = session.train_cfg
    ds = dataset_for_config(session.model_cfg, tc.n_data, tc.seq_len,
                            seed=tc.seed)
    batch = ds.fetch(np.arange(RUN["physical"]))
    mask = np.ones(RUN["physical"], np.float32)
    mask[-3:] = 0.0
    return batch, mask


def session_for(engine: str, *, tile=None, **kw):
    """make_session on RUN; ``tile`` fixes the streaming tile instead of
    the memory budget's choice, so two runs compare per-example backwards
    of one width (bf16 activations round differently at another width)."""
    from repro.launch.train import make_session
    session = make_session(ARCH, engine=engine, **dict(RUN, **kw))
    if tile:
        session.dp = dataclasses.replace(session.dp, stream_tile=tile)
    return session


def accumulate_first_batch(session, precision="default"):
    """One accumulate of first_batch, in physical batches of the
    session's own size; returns grad_acc."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    batch, mask = first_batch(session)
    n = session.train_cfg.physical_batch
    with jax.default_matmul_precision(precision):
        for i in range(0, len(mask), n):
            session.accumulate(jax.tree.map(lambda x: x[i:i + n], batch),
                               mask[i:i + n])
    return jnp.asarray(np.asarray(session.state.grad_acc))


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# phase A: the normal entry point
# ---------------------------------------------------------------------------

def train_engine(check: Checks, engine: str) -> None:
    session = session_for(engine)
    batch, mask = first_batch(session)
    t0 = time.perf_counter()
    acc = session.compiled("accumulate", batch, mask)
    t_acc = time.perf_counter() - t0
    t0 = time.perf_counter()
    upd = session.compiled("update")
    t_upd = time.perf_counter() - t0
    ma = acc.memory_analysis()
    print(f"  {engine}: compile accumulate {t_acc:.1f} s, update "
          f"{t_upd:.1f} s; accumulate temp {ma.temp_size_in_bytes / 1e9:.2f}"
          f" GB, arguments {ma.argument_size_in_bytes / 1e9:.2f} GB",
          flush=True)
    check(f"{engine} accumulate has tpu_custom_call", has_kernel(acc),
          "True", has_kernel(acc))
    check(f"{engine} update has tpu_custom_call", has_kernel(upd),
          "True", has_kernel(upd))

    out = session.fit()
    hist = out["history"]
    losses = [float(r["loss"]) for r in hist]
    eps = [float(r["eps"]) for r in hist]
    expected = [float(e) for e in
                session.describe()["expected_eps_trajectory"]]
    print(f"  {engine}: sigma {out['sigma']:.6f}, logical batches "
          f"{[r['logical_batch'] for r in hist]}", flush=True)
    check(f"{engine} losses", losses, "finite, one per step",
          len(losses) == RUN["steps"]
          and all(math.isfinite(x) for x in losses))
    check(f"{engine} eps", eps, f"== expected trajectory {expected}",
          eps == expected)


def phase_a(check: Checks) -> None:
    for engine in ENGINES:
        train_engine(check, engine)
        gc.collect()


# ---------------------------------------------------------------------------
# phase B: correctness on the chip against the plain paths
# ---------------------------------------------------------------------------

def rel_l2(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


def corr(x, y) -> float:
    import jax.numpy as jnp
    x = x - x.mean()
    y = y - y.mean()
    return float((x * y).mean() / jnp.sqrt((x * x).mean() * (y * y).mean()))


def phase_b(check: Checks) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import tree_noisy_update
    from repro.utils.params import FlatGradView

    # the same 16 rows through each engine at one per-example width (the
    # streaming tile, and masked_pe's physical batch), at two matmul
    # precisions.  The chip's default multiplies f32 in one bf16 pass, and
    # programs that differ in shape round differently; at "highest" the
    # engines must agree to f32 rounding.  Each session is dropped before
    # the next is built, except the last: its state feeds the update check
    accs = {}
    for precision in ("highest", "default"):
        for engine in ("masked_fused_stream", "masked_pe"):
            if engine == "masked_pe":
                session = session_for(engine, physical=PB_WIDTH)
            else:
                session = session_for(engine, tile=PB_WIDTH)
            accs[engine, precision] = accumulate_first_batch(session,
                                                             precision)
            if engine != "masked_pe" or precision != "default":
                del session
                gc.collect()
    r = rel_l2(accs["masked_fused_stream", "highest"],
               accs["masked_pe", "highest"])
    check("grad_acc rel L2, masked_fused_stream vs masked_pe, highest "
          "precision", r, f"<= {ACC_REL_L2}", r <= ACC_REL_L2)
    err = rel_l2(accs["masked_pe", "default"], accs["masked_pe", "highest"])
    check("grad_acc rel L2, masked_pe at default vs highest precision", err,
          f"<= {ACC_DEFAULT_MAX}", err <= ACC_DEFAULT_MAX)
    r = rel_l2(accs["masked_fused_stream", "default"],
               accs["masked_pe", "highest"])
    check("grad_acc rel L2, masked_fused_stream at default vs masked_pe at "
          "highest precision", r, f"<= 2 x masked_pe's = {2 * err:.3g}",
          r <= 2 * err)
    del accs

    # update: Pallas kernel with the noise operand vs the XLA expression
    state = session.state
    dp, view = session.dp, FlatGradView.for_tree(state.params)
    sigma_c, L = dp.noise_multiplier * dp.clip_norm, dp.expected_batch_size
    key, mkey = jax.random.split(jax.random.PRNGKey(RUN["seed"] + 7))
    # a momentum buffer as fit() keeps it: nonzero over the parameters,
    # exactly zero over the alignment tail
    mom = jax.random.normal(mkey, (view.total,), jnp.float32) * 1e-3
    mom = mom.at[view.n_params:].set(0.0)

    def update(use_kernel):
        return jax.jit(lambda p, a, m, k: tree_noisy_update(
            p, a, k, sigma_c, L, 1e-3, momentum_buf=m, momentum=0.9,
            view=view, use_kernel=use_kernel, in_kernel_rng=False))

    pk, mk = update(True)(state.params, state.grad_acc, mom, key)
    px, mx = update(False)(state.params, state.grad_acc, mom, key)
    # the two paths round p - lr*(mu*m + (a + sc*z)/L) in different orders
    # (FMA or not): they may differ by several f32 roundings of
    # |p| + lr*|m|, while a wrong lr, scale, noise or momentum would differ
    # by orders of magnitude more
    n = view.n_params
    scale = (jnp.abs(view.flatten(state.params)[:n])
             + 1e-3 * jnp.abs(mx[:n]))
    err = float(jnp.max(jnp.abs(view.flatten(pk)[:n] - view.flatten(px)[:n])
                        / jnp.maximum(scale, 1e-30)))
    check("new params, update kernel vs XLA, max |dp| / (|p| + lr|m|)", err,
          f"<= {UPDATE_REL_ELEM:.3g}", err <= UPDATE_REL_ELEM)
    r = rel_l2(mk[:view.n_params], mx[:view.n_params])
    check("momentum rel L2, update kernel vs XLA", r,
          f"<= {UPDATE_REL_L2}", r <= UPDATE_REL_L2)
    zeros = jax.tree.map(jnp.zeros_like, state.params)
    del pk, mk, px, mx, mom, state
    del session
    gc.collect()
    noise_stats(check, zeros, view)


def noise_stats(check: Checks, params, view) -> None:
    """The in-kernel hardware-PRNG noise, read back exactly: with params,
    accumulator and momentum at zero, sigma*C = 1, L = 1 and lr = -1 the
    fused update writes z itself into every parameter."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import tree_noisy_update

    zero = jnp.zeros((view.total,), jnp.float32)
    draw = jax.jit(lambda k: tree_noisy_update(
        params, zero, k, 1.0, 1.0, -1.0, momentum_buf=zero, momentum=0.0,
        view=view, use_kernel=True, in_kernel_rng=True)[0])
    k1, k2 = jax.random.split(jax.random.PRNGKey(RUN["seed"] + 11))
    leaves1 = jax.tree.leaves(draw(k1))
    z1 = jnp.concatenate([x.reshape(-1) for x in leaves1])
    n = z1.size
    mean, std = float(z1.mean()), float(z1.std())
    b = SIGMAS / math.sqrt(n)
    check("noise mean", mean, f"|.| <= {b:.3g} (N={n})", abs(mean) <= b)
    b = SIGMAS / math.sqrt(2 * n)
    check("noise std", std, f"|. - 1| <= {b:.3g}", abs(std - 1.0) <= b)
    check("noise finite", bool(jnp.isfinite(z1).all()), "True",
          bool(jnp.isfinite(z1).all()))

    # neighbouring leaves (each with its own seed), over a common prefix
    k = 1 << 16
    big = [x.reshape(-1)[:k] for x in leaves1 if x.size >= k]
    worst = max(abs(corr(a, c)) for a, c in zip(big, big[1:]))
    b = SIGMAS / math.sqrt(k)
    check(f"max |corr| between neighbouring leaves ({len(big)} leaves)",
          worst, f"<= {b:.3g}", worst <= b)

    # neighbouring kernel tiles (programs) of the largest leaf
    from repro.kernels.noisy_update import TILE
    largest = max(leaves1, key=lambda x: x.size).reshape(-1)
    t = largest.size // TILE
    tiles = largest[:t * TILE].reshape(t, TILE)
    c = corr(tiles[:-1].reshape(-1), tiles[1:].reshape(-1))
    b = SIGMAS / math.sqrt((t - 1) * TILE)
    check(f"corr between neighbouring tiles ({t} tiles of {TILE})", c,
          f"|.| <= {b:.3g}", abs(c) <= b)

    # two steps (two keys) draw different noise
    z2 = jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(draw(k2))])
    c = corr(z1, z2)
    b = SIGMAS / math.sqrt(n)
    check("corr between two steps' draws", c, f"|.| <= {b:.3g}",
          abs(c) <= b)
    same = float((z1 == z2).mean())
    check("share of equal elements across two steps", same, "<= 1e-6",
          same <= 1e-6)


# ---------------------------------------------------------------------------
# phase C: the privacy verifier on the program the chip traces
# ---------------------------------------------------------------------------

def phase_c(check: Checks) -> None:
    import jax
    from repro.analysis.verify import verify_session
    session = session_for("masked_fused_stream")
    report = verify_session(session)
    print("  " + str(report).replace("\n", "\n  "), flush=True)
    check("verifier violations", len(report.violations), "== 0", report.ok)
    # the in-kernel-noise branch declares one noise mark per leaf; the
    # noise-operand branch declares one for the whole buffer
    leaves = len(jax.tree.leaves(session.state.params))
    marks = report.stats.get("noise_marks")
    check("noise marks (in-kernel noise traced)", marks,
          f"== {leaves} leaves", marks == leaves)


# ---------------------------------------------------------------------------
# four chips: data-parallel fit against the same fit on one chip
# ---------------------------------------------------------------------------

def phase_dp4(check: Checks) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.utils.params import FlatGradView

    runs, accs = {}, {}
    for name, mesh, tile in (("1 chip", None, DP4_TILE // 4),
                             ("4 chips", (4,), DP4_TILE)):
        # one accumulate of the first batch at "highest" matmul precision
        # and the same per-example width on every chip (as in phase B),
        # then a fresh session's fit as a user runs it (a mesh session
        # donates its state to each step)
        session = session_for("masked_fused_stream", tile=tile, mesh=mesh)
        accs[name] = accumulate_first_batch(session, "highest")
        del session
        gc.collect()
        session = session_for("masked_fused_stream", mesh=mesh)
        view = FlatGradView.for_tree(session.state.params)
        p0 = np.asarray(view.flatten(session.state.params))
        out = session.fit()
        p = np.asarray(view.flatten(session.state.params))
        eps = session.privacy_spent()[0]
        print(f"  {name}: {session.executor.describe()}, losses "
              f"{[r['loss'] for r in out['history']]}, eps {eps!r}",
              flush=True)
        runs[name] = (p0, p, eps)
        del session
        gc.collect()
    (p0, p1, e1), (q0, p4, e4) = runs["1 chip"], runs["4 chips"]
    check("initial params equal", bool(np.array_equal(p0, q0)), "True",
          bool(np.array_equal(p0, q0)))
    r = rel_l2(accs["4 chips"], accs["1 chip"])
    check("grad_acc rel L2, 4 chips vs 1 chip, highest precision", r,
          f"<= {DP4_ACC_REL_L2}", r <= DP4_ACC_REL_L2)
    r = rel_l2(jnp.asarray(p4 - p0), jnp.asarray(p1 - p0))
    check("param update rel L2, 4 chips vs 1 chip", r, f"<= {DP4_REL_L2}",
          r <= DP4_REL_L2)
    check("eps, 4 chips vs 1 chip", f"{e4.hex()} vs {e1.hex()}",
          "bit-identical", e4 == e1)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the data-parallel fit on four chips "
                         "against the same fit on one")
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX's default backend is {backend!r}, not a TPU; "
              f"there is no fallback", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {devices[0].device_kind} x {len(devices)}; compile "
          f"cache {enable_compile_cache()}", flush=True)

    check = Checks()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase("four chips: data-parallel fit vs one chip", check, phase_dp4)
    else:
        phase("A: normal entry point, vit-base full width", check, phase_a)
        phase("B: chip vs plain paths", check, phase_b)
        phase("C: privacy verifier on the chip's step", check, phase_c)
    print(f"total {time.perf_counter() - t0:.1f} s", flush=True)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
