"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows; benches with a JSON payload
also refresh their ``BENCH_*.json`` record at the repo root (the across-PR
trajectory is those files' git history).

  bench_throughput  -> Fig. 1 / Fig. 4   (throughput by clipping engine)
  bench_memory      -> Fig. 3 / Table 3  (max physical batch / memory wall)
  bench_recompile   -> Fig. A.2 / §6     (naive vs masked recompilation)
  bench_precision   -> Fig. 5            (TF32 -> bf16/relaxed-matmul analogue)
  bench_breakdown   -> Table 2           (fwd/bwd/clip/opt section costs)
  bench_step        -> Table 2, per engine, through the REAL session paths +
                       the fused-update bytes-accessed assertions
  bench_scaling     -> Fig. 7 / Fig. A.5 (multi-chip scaling, DP vs SGD)
  bench_batchsize   -> Fig. A.1          (throughput vs physical batch size)
  bench_serving     -> (beyond the paper) static vs continuous vs chunked
                       prefill vs prefix sharing on a shared-prefix trace
  bench_sampler     -> Table 1 extended: throughput at EQUAL eps across the
                       registered sampler menu (shuffle charged UNAMPLIFIED)

``--smoke`` runs the CI subset (bench_step + bench_memory + bench_breakdown
+ bench_serving on reduced configs) — fast enough for the 8-device job,
still exercising the session/engine bench plumbing, the one-pass and
streaming-traffic assertions and the serving token-identity assert so the
benches can't bit-rot.
"""
import argparse
import inspect
import sys
import traceback


def _modules():
    try:
        from . import (bench_batchsize, bench_breakdown, bench_memory,
                       bench_precision, bench_recompile, bench_sampler,
                       bench_scaling, bench_serving, bench_step,
                       bench_throughput)
    except ImportError:
        # `python benchmarks/run.py` (no package context, e.g. the CI smoke
        # step): import absolutely with the repo root on sys.path
        import os
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from benchmarks import (bench_batchsize, bench_breakdown,
                                bench_memory, bench_precision,
                                bench_recompile, bench_sampler,
                                bench_scaling, bench_serving, bench_step,
                                bench_throughput)
    all_mods = (bench_throughput, bench_memory, bench_recompile,
                bench_precision, bench_breakdown, bench_step, bench_scaling,
                bench_batchsize, bench_serving, bench_sampler)
    smoke_mods = (bench_step, bench_memory, bench_breakdown, bench_serving,
                  bench_sampler)
    return all_mods, smoke_mods


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: bench_step + bench_memory + "
                         "bench_breakdown + bench_serving (reduced)")
    ap.add_argument("--only", default=None,
                    help="run a single bench by name (e.g. bench_step)")
    ap.add_argument("--metrics", action="store_true",
                    help="benches that support it run an instrumented pass "
                         "and assert the observability overhead budget "
                         "(bench_serving: sampled-vs-off elapsed <= 1.05x)")
    args = ap.parse_args(argv)

    all_mods, smoke_mods = _modules()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    mods = smoke_mods if args.smoke else all_mods
    if args.only:
        byname = {m.__name__.rsplit(".", 1)[-1]: m for m in all_mods}
        if args.only not in byname:
            ap.error(f"unknown bench {args.only!r}; "
                     f"expected one of {sorted(byname)}")
        mods = (byname[args.only],)

    print("name,us_per_call,derived")
    ok = True
    for mod in mods:
        try:
            # benches with a smoke/metrics mode take the flag as a kwarg
            params = inspect.signature(mod.main).parameters
            kwargs = {}
            if args.smoke and "smoke" in params:
                kwargs["smoke"] = True
            if args.metrics and "metrics" in params:
                kwargs["metrics"] = True
            mod.main(**kwargs)
        except Exception:
            ok = False
            traceback.print_exc()
            print(f"{mod.__name__},FAILED,", file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
