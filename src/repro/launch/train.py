"""End-to-end DP-SGD training driver (runs on CPU with reduced configs).

A thin CLI over :class:`repro.core.session.PrivacySession`, which owns the
full stack the way a real deployment would:
  PoissonSampler -> BatchMemoryManager -> clipping engine -> accountant ->
  optimizer -> checkpoint.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 4 --engine masked_pe --target-eps 8.0
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

from ..core import DPConfig, clipping
from ..core.session import PrivacySession, TrainConfig
from ..data import available_samplers
from ..data.synthetic import dataset_for_config
from ..obs import add_cli_args, config_from_args, start_profile, stop_profile
from .compile_cache import enable_compile_cache
from .executor import LaunchConfig


def make_dataset(cfg, n, seq_len, seed=0):
    """Back-compat alias for repro.data.synthetic.dataset_for_config."""
    return dataset_for_config(cfg, n, seq_len, seed=seed)


def make_session(arch: str, *, smoke: bool = True, steps: int = 4,
                 n_data: int = 512, seq_len: int = 16, physical: int = 8,
                 q: float = 0.25, sampler: str = "poisson",
                 engine: str = "masked_pe",
                 target_eps: float = 8.0, delta: Optional[float] = None,
                 clip_norm: float = 1.0, lr: float = 1e-3,
                 optimizer: str = "sgd", seed: int = 0,
                 microbatches: int = 1, log_every: int = 1,
                 mesh: Optional[str] = None, layout: str = "dp",
                 obs=None) -> PrivacySession:
    """The one place the training CLI wires configs into a PrivacySession.

    ``mesh`` (a LaunchConfig preset: "test", "production", ...) runs the same
    fit() sharded through the MeshExecutor — sharded DP-SGD is a config
    value, not a separate script."""
    dp = DPConfig(clip_norm=clip_norm, engine=engine,
                  microbatches=microbatches)
    tc = TrainConfig(steps=steps, n_data=n_data, seq_len=seq_len,
                     physical_batch=physical, q=q, sampler=sampler,
                     target_eps=target_eps if engine != "nonprivate" else None,
                     delta=delta, lr=lr, optimizer=optimizer, smoke=smoke,
                     seed=seed, log_every=log_every)
    launch = LaunchConfig(mesh=mesh, layout=layout)
    return PrivacySession.from_config(arch, dp, tc, launch=launch, obs=obs)


def train(arch: str, *, smoke: bool = True, steps: int = 4, n_data: int = 512,
          seq_len: int = 16, physical: int = 8, q: float = 0.25,
          sampler: str = "poisson",
          engine: str = "masked_pe", target_eps: float = 8.0,
          delta: Optional[float] = None, clip_norm: float = 1.0, lr: float = 1e-3,
          optimizer: str = "sgd", seed: int = 0, ckpt: Optional[str] = None,
          log_every: int = 1, describe: bool = False,
          mesh: Optional[str] = None, layout: str = "dp", obs=None,
          profile_dir: Optional[str] = None) -> dict:
    session = make_session(arch, smoke=smoke, steps=steps, n_data=n_data,
                           seq_len=seq_len, physical=physical, q=q,
                           sampler=sampler, engine=engine,
                           target_eps=target_eps, delta=delta,
                           clip_norm=clip_norm, lr=lr, optimizer=optimizer,
                           seed=seed, log_every=log_every, mesh=mesh,
                           layout=layout, obs=obs)
    if describe:
        print(json.dumps(session.describe()))
    if profile_dir:
        start_profile(profile_dir)
    try:
        out = session.fit(ckpt=ckpt)
    finally:
        if profile_dir:
            stop_profile()
        if session.obs.enabled:
            print(session.obs.snapshot(), file=sys.stderr)
        session.obs.close()
    for rec in out["history"]:
        print(json.dumps(rec))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--n-data", type=int, default=256)
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--physical", type=int, default=8)
    ap.add_argument("--q", type=float, default=0.25)
    ap.add_argument("--sampler", default="poisson",
                    choices=available_samplers(),
                    help="registered sampler (accounting follows the "
                         "sampler's declared bound: shuffle/full_batch are "
                         "charged UNAMPLIFIED)")
    ap.add_argument("--engine", default="masked_pe",
                    choices=sorted([*clipping.ENGINES, "nonprivate"]))
    ap.add_argument("--mesh", default=None,
                    help="LaunchConfig mesh preset (e.g. test, production); "
                         "default: local, unsharded")
    ap.add_argument("--layout", default="dp", choices=["dp", "dp_sp", "2d"])
    ap.add_argument("--target-eps", type=float, default=8.0)
    ap.add_argument("--clip-norm", type=float, default=1.0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--describe", action="store_true",
                    help="print the session report before training")
    ap.add_argument("--ckpt")
    add_cli_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    out = train(args.arch, smoke=args.smoke, steps=args.steps,
                n_data=args.n_data, seq_len=args.seq_len,
                physical=args.physical, q=args.q, sampler=args.sampler,
                engine=args.engine,
                target_eps=args.target_eps, clip_norm=args.clip_norm,
                lr=args.lr, optimizer=args.optimizer, seed=args.seed,
                ckpt=args.ckpt, describe=args.describe, mesh=args.mesh,
                layout=args.layout, obs=config_from_args(args),
                profile_dir=args.profile_dir)
    print(json.dumps({"final": out["history"][-1] if out["history"] else {},
                      "sigma": round(out["sigma"], 4),
                      "final_eps": round(out["final_eps"], 4)}))


if __name__ == "__main__":
    main()
