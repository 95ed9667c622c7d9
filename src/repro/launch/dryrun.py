import os
# force the 512 host devices the production mesh needs, PRESERVING any other
# user-set XLA flags; tests override by setting their own device count first
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512"
                               ).strip()

"""Multi-pod dry-run: lower + compile every (arch x input-shape) combination
on the production mesh, with ShapeDtypeStruct inputs (no allocation).

For train/prefill shapes this lowers the fused DP-SGD step (clip + noise +
update); for decode shapes it lowers serve_step (one token against a KV/SSM
cache of seq_len).  Prints memory_analysis / cost_analysis / collective
inventory and emits a JSON record consumed by the roofline report.

All mesh construction, sharding resolution and jit plumbing goes through
:class:`repro.launch.executor.MeshExecutor` — the same code path
``PrivacySession.fit()`` executes when built with a mesh LaunchConfig, so
what is lowered here is what runs there.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k [--multi-pod] [--engine masked_pe] [--unroll]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out runs/dryrun
"""
import argparse
import dataclasses
import json
import math
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import SHAPES, input_specs
from ..core import DPConfig, build_fused_step, init_state
from ..core.tape import set_remat, set_scan_unroll
from ..models import build, get_config
from ..optim import sgd
from . import costmodel, hlo
from .executor import LaunchConfig, MeshExecutor
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

# Skips mandated by the assignment (full-attention archs on long_500k);
# qwen3 runs it via its sliding-window variant.
LONG_OK = {"mamba2-1.3b", "zamba2-1.2b", "qwen3-1.7b"}

# Paper-faithful Algorithm 2 (masked per-example vmap clipping) where the
# per-example gradient memory wall allows; ghost elsewhere (identical update
# values — see DESIGN.md).  Microbatches = in-step physical batching
# (Algorithm 1's virtual batching inside the jitted step).
DEFAULT_ENGINE = {
    "qwen2-0.5b": "masked_pe",
    "whisper-base": "masked_pe",
    "vit-base": "masked_pe",
}
FALLBACK_ENGINE = "masked_ghost"
GIANTS = ("deepseek-67b", "llama-3.2-vision-90b")
DEFAULT_MICROBATCH = {"deepseek-67b": 16, "llama-3.2-vision-90b": 16}
DEFAULT_MB_OTHER = 16


def _arch_config(arch: str, shape_name: str):
    cfg = get_config(arch)
    if shape_name == "long_500k" and arch == "qwen3-1.7b":
        cfg = dataclasses.replace(cfg, sliding_window=4096,
                                  name="qwen3-1.7b-swa")
    return cfg


def applicable(arch: str, shape_name: str) -> bool:
    if shape_name == "long_500k" and arch not in LONG_OK:
        return False
    if arch == "vit-base" and shape_name != "train_4k":
        return False        # classifier: no decode/prefill serving shapes
    return True


def lower_one(arch: str, shape_name: str, *, mesh: str = "production",
              engine: Optional[str] = None, microbatches: Optional[int] = None,
              unroll: bool = False, compile_: bool = True,
              layout: str = "2d", ce_chunk: int = 512,
              pe_bf16: bool = False, remat: bool = False,
              smoke: bool = False, prefill_chunk: int = 0,
              verify: bool = False, sampler: str = "poisson") -> dict:
    cfg = _arch_config(arch, shape_name)
    if smoke:
        cfg = cfg.reduced()
    if ce_chunk and shape_name.startswith("train"):
        cfg = dataclasses.replace(cfg, ce_chunk=ce_chunk)
    if remat or shape_name.startswith("train"):
        # activation checkpointing on every plain-mode layer scan (the ghost
        # record passes keep their records; pass-2/pe backwards recompute)
        cfg = dataclasses.replace(cfg, remat=True)
    shape = SHAPES[shape_name]
    executor = MeshExecutor(LaunchConfig(mesh=mesh, layout=layout,
                                         pe_bf16=pe_bf16))
    chips = math.prod(executor.mesh.shape.values())
    model = build(cfg)
    engine = engine or DEFAULT_ENGINE.get(arch, FALLBACK_ENGINE)
    mb = microbatches if microbatches is not None else \
        DEFAULT_MICROBATCH.get(arch, DEFAULT_MB_OTHER)
    set_scan_unroll(cfg.n_layers if unroll else 1)
    # flash attention from 4k up; the executor decides sequence-parallel
    # activations / expert-parallel dispatch for this layout (see DESIGN.md)
    from ..models import common as cm_mod
    cm_mod.set_flash_min_t(4096)
    executor.configure_model(cfg, shape.kind, shape.seq_len,
                             shape.global_batch, engine)
    set_remat(cfg.remat)

    # sharding constraints resolved by the executor for this layout/engine —
    # the exact ShardingConstraints a mesh session would train with
    constraints = executor.constraints(engine)

    # resolve through the registry (unknown names fail listing what IS
    # registered) and record the accounting the planned run would be
    # charged under — dry-run reports must not imply amplification a
    # shortcut sampler doesn't have
    from ..data.sampler import resolve_sampler
    sampler_cls = resolve_sampler(sampler)
    rec = {"arch": arch, "shape": shape_name, "kind": shape.kind,
           "mesh": dict(executor.mesh.shape), "engine": engine,
           "microbatches": mb, "unrolled": bool(unroll),
           "sampler": {"kind": sampler, "accounting": sampler_cls.accounting}}
    t0 = time.time()

    if shape.kind == "prefill":
        # inference prefill: full-sequence forward producing logits
        # (shape-only: eval_shape never runs the init)  lint: allow-const-key
        params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        specs = input_specs(cfg, shape)

        def prefill_step(params, batch):
            # last-position logits only (XLA pushes the slice into the head
            # matmul — the full (B,32k,V) logits never materialise; §Perf)
            from ..core.tape import Tape
            t = Tape()
            if cfg.family in ("vlm", "audio"):
                return model.logits(params, batch["tokens"],
                                    batch["frontend"], t, last_only=True)
            if cfg.family == "moe":
                return model.logits_aux(params, batch["tokens"], t,
                                        last_only=True)[0]
            return model.logits(params, batch["tokens"], t, last_only=True)

        lowered = executor.lower_prefill(prefill_step, params_shape,
                                         specs["batch"])
        costs = costmodel.train_costs(model, cfg, shape, "nonprivate",
                                      dict(executor.mesh.shape))
        # forward-only: one pass instead of three
        costs = dataclasses.replace(
            costs, flops=costs.flops / 3.0,
            hbm_bytes=costs.hbm_bytes / 3.0,
            coll_bytes=costs.coll_bytes / 2.0,
            model_flops=costs.model_flops / 3.0)
    elif shape.kind == "train":
        dpc = DPConfig(clip_norm=1.0, noise_multiplier=1.0,
                       expected_batch_size=shape.global_batch,
                       engine=engine, microbatches=mb)
        opt = sgd(1e-3, momentum=0.9)
        state_shape = jax.eval_shape(          # lint: allow-const-key
            lambda: init_state(model.init(jax.random.PRNGKey(0)), opt,
                               jax.random.PRNGKey(1)))  # lint: allow-const-key
        specs = input_specs(cfg, shape)
        step = build_fused_step(lambda p, b, t: model.loss(p, b, t), opt, dpc,
                                constraints=constraints)
        lowered = executor.lower_train(step, state_shape, specs["batch"],
                                       specs["mask"])
        if verify:
            # taint-check EXACTLY the program lowered above: same step fn,
            # shapes, shardings and donation, through the trace_train seam
            from ..analysis.verify import verify_trace
            closed, out_info = executor.trace_train(
                step, state_shape, specs["batch"], specs["mask"])
            report = verify_trace(
                closed, out_info, state_shape, specs["batch"],
                private=dpc.private,
                sigma_c=dpc.noise_multiplier * dpc.clip_norm,
                target=f"{arch} x {engine} x {layout} ({shape_name})")
            print(report)
            rec["verify"] = {"ok": report.ok,
                             "violations": [str(v) for v in
                                            report.violations]}
            if not report.ok:
                raise SystemExit(
                    f"privacy verification FAILED for {arch} {shape_name}")
        costs = costmodel.train_costs(model, cfg, shape, engine,
                                      dict(executor.mesh.shape))
    else:
        params_shape = jax.eval_shape(         # lint: allow-const-key
            lambda: model.init(jax.random.PRNGKey(0)))
        cache_shape = jax.eval_shape(
            lambda p: model.init_cache(p, shape.global_batch, shape.seq_len),
            params_shape)
        tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        # per-slot position vector — the shape the serving engine decodes with
        pos = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)

        def serve_step(params, cache, tokens, p):
            return model.decode_step(params, cache, tokens, p)

        lowered = executor.lower_decode(serve_step, params_shape, cache_shape,
                                        tok, pos)
        if prefill_chunk > 1 and hasattr(model, "prefill_step"):
            # the serving engine's OTHER jit entry point: one fused call
            # consuming (B, C) prompt tokens at per-slot offsets — lowered
            # through the same executor path the engine executes
            t_pf = time.time()
            tok_c = jax.ShapeDtypeStruct(
                (shape.global_batch, prefill_chunk), jnp.int32)
            ntok = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)

            def chunk_step(params, cache, tokens, p, n):
                return model.prefill_step(params, cache, tokens, p, n)

            pf_lowered = executor.lower_prefill_step(
                chunk_step, params_shape, cache_shape, tok_c, pos, ntok)
            rec["prefill_chunk"] = prefill_chunk
            rec["prefill_lower_s"] = round(time.time() - t_pf, 2)
            if compile_:
                t_pf = time.time()
                pf_lowered.compile()
                rec["prefill_compile_s"] = round(time.time() - t_pf, 2)
        costs = costmodel.decode_costs(model, cfg, shape,
                                       dict(executor.mesh.shape))

    rec["lower_s"] = round(time.time() - t0, 2)
    if not compile_:
        return rec

    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "per_device_total": (ma.argument_size_in_bytes
                             + ma.temp_size_in_bytes
                             + ma.output_size_in_bytes
                             - ma.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    rec["hlo_cost"] = {"flops": ca.get("flops", -1.0),
                       "bytes_accessed": ca.get("bytes accessed", -1.0),
                       "transcendentals": ca.get("transcendentals", -1.0)}

    L_eff = 1 if unroll else max(cfg.n_layers, 1)
    if shape.kind == "train":
        depth_factors = [mb, mb * L_eff, mb * L_eff]
    else:
        depth_factors = [L_eff, L_eff]
    rec["collectives"] = hlo.summarize(compiled.as_text(), depth_factors)
    coll_measured = rec["collectives"]["total_bytes"]

    # roofline terms (seconds); collective term from the compiled schedule
    # (per-device shard bytes x loop trip counts), analytic as cross-check
    rec["analytic"] = {
        "flops": costs.flops, "hbm_bytes": costs.hbm_bytes,
        "coll_bytes_per_dev": costs.coll_bytes,
        "model_flops": costs.model_flops,
        "n_params": costs.n_params, "n_active": costs.n_active,
        "detail": costs.detail,
    }
    rec["roofline"] = {
        "t_compute": costs.flops / (chips * PEAK_FLOPS_BF16),
        "t_memory": costs.hbm_bytes / (chips * HBM_BW),
        "t_collective": coll_measured / ICI_BW,
        "t_collective_analytic": costs.coll_bytes / ICI_BW,
        "useful_ratio": costs.model_flops / max(costs.flops, 1.0),
    }
    rec["roofline"]["dominant"] = max(
        ("t_compute", "t_memory", "t_collective"),
        key=lambda k: rec["roofline"][k])
    rec["fits_hbm"] = rec["memory"]["per_device_total"] <= 16 * 2 ** 30
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", default=None,
                    choices=["test", "production", "production-multipod"],
                    help="mesh preset (default: production; --multi-pod "
                         "selects production-multipod)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--engine")
    ap.add_argument("--microbatches", type=int)
    ap.add_argument("--unroll", action="store_true")
    ap.add_argument("--layout", default="2d", choices=["2d", "dp", "dp_sp"])
    ap.add_argument("--ce-chunk", type=int, default=512)
    ap.add_argument("--pe-bf16", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced model configs (CPU-testable lowering)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="also lower the serving engine's chunked "
                         "prefill_step at this chunk size for decode shapes "
                         "(0 = skip)")
    ap.add_argument("--sampler", default="poisson",
                    help="registered sampler the planned run would use; "
                         "recorded (with its accounting bound) in the "
                         "dry-run report")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--verify", action="store_true",
                    help="taint-check the DP invariants of each lowered "
                         "train step (repro.analysis); fails the combo on "
                         "any violation")
    ap.add_argument("--out", default=None, help="directory for JSON records")
    args = ap.parse_args()
    if args.mesh and args.multi_pod and args.mesh != "production-multipod":
        ap.error(f"--multi-pod conflicts with --mesh {args.mesh}; "
                 f"pass one or the other")
    mesh = args.mesh or ("production-multipod" if args.multi_pod
                         else "production")

    from ..models.registry import ARCH_IDS
    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                if applicable(a, s):
                    combos.append((a, s))
    else:
        combos = [(args.arch, args.shape)]

    ok = fail = 0
    for arch, shape in combos:
        try:
            rec = lower_one(arch, shape, mesh=mesh,
                            engine=args.engine, microbatches=args.microbatches,
                            unroll=args.unroll, compile_=not args.no_compile,
                            layout=args.layout, ce_chunk=args.ce_chunk,
                            pe_bf16=args.pe_bf16, remat=args.remat,
                            smoke=args.smoke,
                            prefill_chunk=args.prefill_chunk,
                            verify=args.verify, sampler=args.sampler)
            rec["status"] = "ok"
            ok += 1
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "status": "fail",
                   "error": f"{type(e).__name__}: {e}"}
            fail += 1
        print(json.dumps({k: v for k, v in rec.items()
                          if k not in ("analytic",)}, default=str))
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            # sp/mp are the roofline report's buckets; other meshes get
            # their own tag so they never pollute production records
            tag = {"production": "sp", "production-multipod": "mp"}.get(
                mesh, mesh)
            with open(os.path.join(
                    args.out, f"{arch}__{shape}__{tag}.json"), "w") as f:
                json.dump(rec, f, indent=1, default=str)
    print(f"\nDRYRUN SUMMARY: {ok} ok, {fail} failed / {len(combos)}")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
