"""Shared benchmark helpers (CPU wall-clock on reduced configs).

All benchmarks construct training through PrivacySession — the same audited
DP path the launch drivers use — via :func:`make_session`.
"""
import sys
import os
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.core import DPConfig
from repro.core.session import PrivacySession, TrainConfig
from repro.models import build_by_name


def make_session(arch, engine="masked_pe", B=8, *, clip_norm=1.0,
                 noise_multiplier=1.0, microbatches=1, lr=1e-3,
                 momentum=0.0, optimizer="sgd", seed=0,
                 model_cfg=None, stream_tile=None) -> PrivacySession:
    """A benchmark session: expected logical batch pinned to the physical
    batch B (benchmarks time fixed-size steps, not Poisson draws)."""
    if model_cfg is not None:
        from repro.models import build
        model, cfg = build(model_cfg), model_cfg
    else:
        model, cfg = build_by_name(arch, smoke=True)
    dp = DPConfig(clip_norm=clip_norm, noise_multiplier=noise_multiplier,
                  expected_batch_size=float(B), engine=engine,
                  microbatches=microbatches, stream_tile=stream_tile)
    tc = TrainConfig(physical_batch=B, lr=lr, optimizer=optimizer,
                     momentum=momentum, seed=seed)
    return PrivacySession(model, cfg, dp, tc)


def timeit(fn, *args, warmup=1, iters=3):
    """Median wall time (s) of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def make_lm_batch(cfg, B, T, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    if cfg.family == "vit":
        return {"image": jax.random.normal(
                    ks[0], (B, cfg.image_size, cfg.image_size, 3)),
                "label": jax.random.randint(ks[1], (B,), 0, cfg.n_classes)}
    b = {"tokens": jax.random.randint(ks[0], (B, T), 0, cfg.vocab),
         "labels": jax.random.randint(ks[1], (B, T), 0, cfg.vocab)}
    if cfg.family == "vlm":
        b["frontend"] = jax.random.normal(
            ks[2], (B, cfg.n_image_tokens, cfg.frontend_dim)) * 0.1
    if cfg.family == "audio":
        b["frontend"] = jax.random.normal(
            ks[2], (B, cfg.n_audio_frames, cfg.d_model)) * 0.1
    return b


def csv_row(name, us_per_call, derived=""):
    print(f"{name},{us_per_call:.1f},{derived}")


def emit_json(filename, payload):
    """Write the latest benchmark record to BENCH_<name>.json at the repo
    root, replacing the previous one — the across-PR trajectory lives in the
    file's git history, not inside the file."""
    import json
    path = os.path.join(os.path.dirname(__file__), "..", filename)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    print(f"# wrote {os.path.normpath(path)}")
    return path


def compiled_cost(fn, *shaped_args):
    """Lower+compile ``fn`` on ShapeDtypeStructs and return
    (bytes_accessed, flops) from XLA's post-optimization cost_analysis —
    the structural numbers the one-pass-vs-multi-pass assertions use."""
    shaped = [jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), a)
        for a in shaped_args]
    c = jax.jit(fn).lower(*shaped).compile()
    ca = c.cost_analysis() or {}
    bytes_ = float(ca.get("bytes accessed", -1.0))
    if bytes_ <= 0:
        # fail loudly rather than let the one-pass assertions compare
        # garbage sentinels (cost_analysis shape drifts across jax versions)
        raise RuntimeError(
            f"cost_analysis returned no usable 'bytes accessed' ({ca!r})")
    return bytes_, float(ca.get("flops", -1.0))
