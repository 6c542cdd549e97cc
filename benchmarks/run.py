"""Benchmark harness: one module per paper table/figure.

  bench_campaign_hotpath— ref-vs-vec campaign engine tests/sec + speedup
                          (writes the repo-root BENCH_campaign.json)
  bench_model_campaign  — model-stack campaigns (lm-train, decode) + delta
                          persist traffic (writes the repo-root BENCH_model.json)
  bench_recomputability — Fig 3 + Fig 6 (fault-model sweep, robustness matrix)
  bench_selection       — Fig 4a/4b + Fig 5
  bench_static_plan     — static analyzer vs measured plans: agreement table
                          + static+verify tests-saved on sor
  bench_adaptive        — adaptive scheduler vs brute force: tests-saved per
                          app + plan-equivalence bars (BENCH_adaptive.json)
  bench_persist_overhead— Table 4
  bench_nvm_writes      — Fig 9
  bench_efficiency      — Fig 10 + Fig 11 (closed-form model)
  bench_sysim           — Fig 10/11 shapes from the failure-trace simulator,
                          driven by campaign-measured recompute profiles
  bench_fleetsim        — replica fleet serving under failures: goodput/SLO/
                          tail latency per policy (repo-root BENCH_fleet.json)
  bench_kernels         — Pallas kernels vs oracles (us/call CSV)
  bench_workflow        — shared-pool orchestrator vs serial workflow engine
  bench_roofline        — §Roofline table from the dry-run artifacts

``python -m benchmarks.run [--full]`` — default is the fast (CI-sized)
configuration; --full uses the paper-sized campaigns.  ``--profile`` wraps
each selected benchmark in cProfile and drops the top-30 cumulative entries
next to its results, so perf work can point at measured hot spots instead
of guessed ones.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def _run_profiled(name: str, fn, fast: bool) -> None:
    import cProfile
    import pstats

    from .common import RESULTS_DIR

    pr = cProfile.Profile()
    pr.enable()
    try:
        fn(fast=fast)
    finally:
        pr.disable()
        os.makedirs(RESULTS_DIR, exist_ok=True)
        path = os.path.join(RESULTS_DIR, f"profile_{name}.txt")
        with open(path, "w") as f:
            stats = pstats.Stats(pr, stream=f)
            stats.sort_stats("cumulative").print_stats(30)
        print(f"[{name}] profile (top-30 cumulative) -> {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument(
        "--profile", action="store_true",
        help="cProfile each selected benchmark; top-30 cumulative entries "
             "are written to benchmarks/results/profile_<name>.txt",
    )
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    fast = not args.full

    from . import (
        bench_adaptive,
        bench_campaign_hotpath,
        bench_efficiency,
        bench_fleetsim,
        bench_kernels,
        bench_model_campaign,
        bench_nvm_writes,
        bench_persist_overhead,
        bench_recomputability,
        bench_roofline,
        bench_selection,
        bench_static_plan,
        bench_sysim,
        bench_workflow,
    )

    benches = [
        ("campaign_hotpath", bench_campaign_hotpath.run),
        ("model_campaign", bench_model_campaign.run),
        ("recomputability", bench_recomputability.run),
        ("fault_sweep", bench_recomputability.fault_sweep),
        ("robustness_matrix", bench_recomputability.robustness_matrix),
        ("workflow_orchestrator", bench_workflow.run),
        ("static_plan", bench_static_plan.run),
        ("adaptive", bench_adaptive.run),
        ("selection", bench_selection.run),
        ("persist_overhead", bench_persist_overhead.run),
        ("nvm_writes", bench_nvm_writes.run),
        ("efficiency", bench_efficiency.run),
        ("sysim", bench_sysim.run),
        ("sysim_frontier", bench_sysim.frontier),
        ("fleetsim", bench_fleetsim.run),
        ("kernels", bench_kernels.run),
        ("roofline", bench_roofline.run),
    ]
    failed = []
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            if args.profile:
                _run_profiled(name, fn, fast)
            else:
                fn(fast=fast)
            print(f"[{name}] done in {time.time()-t0:.0f}s")
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"\nFAILED benches: {failed}")
        sys.exit(1)
    print("\nall benches complete")


if __name__ == "__main__":
    main()
