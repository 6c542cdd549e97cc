"""Paper Table 4: runtime overhead of persistence operations.

Wall-clock measured: per app we time (a) one main-loop iteration, (b) one
EasyCrash persistence op (delta flush of the selected critical objects into
the arena), then derive normalized execution time for: the EasyCrash plan,
persisting all candidates at every iteration ("without selection"), and the
best-recomputability schedule (every region, every iteration).
"""
from __future__ import annotations

import time

import numpy as np

from .common import APPS, campaign_size, emit


def _time_fn(fn, reps=5):
    fn()  # warm-up / jit
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def run(fast: bool = True):
    from repro.core import EasyCrashManager, FlushPolicy, NVMArena
    from repro.core.workflow import WorkflowConfig, run_workflow
    from repro.hpc.suite import bench_app, ci_app, default_cache

    rows = []
    n = campaign_size(fast) // 2
    for name in APPS:
        app = ci_app(name) if fast else bench_app(name)
        cache = default_cache(app)
        wf = run_workflow(app, WorkflowConfig(n_tests=n, cache=cache, seed=0))
        state = app.init(0)
        state = app.run_iteration(state)

        iter_t = _time_fn(lambda: app.run_iteration(state))

        critical = EasyCrashManager(NVMArena(), FlushPolicy(leaves=tuple(wf.critical)))
        every = EasyCrashManager(NVMArena(), FlushPolicy(leaves=tuple(app.candidates)))
        for mgr in (critical, every):
            mgr.maybe_flush(0, state)  # the first flush writes the objects whole
        # the timed calls (and the warm-up, which compiles the mask) are delta
        # flushes of the same state
        flush_t = _time_fn(lambda: critical.maybe_flush(1, state))
        flush_all_t = _time_fn(lambda: every.maybe_flush(1, state))
        # ops per iteration under each schedule
        plan_ops = sum(1.0 / x for x in wf.plan.region_freq.values())
        n_regions = len(app.regions())
        norm_ec = 1.0 + plan_ops * flush_t / max(iter_t, 1e-9)
        norm_all = 1.0 + flush_all_t / max(iter_t, 1e-9)
        norm_best = 1.0 + n_regions * flush_t / max(iter_t, 1e-9)
        rows.append({
            "app": name,
            "persist_once_ms": round(flush_t * 1e3, 3),
            "iter_ms": round(iter_t * 1e3, 3),
            "persist_ops_per_iter": round(plan_ops, 2),
            "norm_time_easycrash": round(norm_ec, 4),
            "norm_time_no_selection": round(norm_all, 4),
            "norm_time_best": round(norm_best, 4),
        })
    avg = lambda k: round(float(np.mean([r[k] for r in rows])), 4)
    rows.append({
        "app": "average",
        "persist_once_ms": avg("persist_once_ms"),
        "iter_ms": avg("iter_ms"),
        "persist_ops_per_iter": avg("persist_ops_per_iter"),
        "norm_time_easycrash": avg("norm_time_easycrash"),
        "norm_time_no_selection": avg("norm_time_no_selection"),
        "norm_time_best": avg("norm_time_best"),
    })
    print(f"[headline] EasyCrash overhead {100*(rows[-1]['norm_time_easycrash']-1):.1f}% "
          f"(paper: 1.5% avg, <=2.5% bounded by t_s=3%)")
    emit(rows, "persist_overhead")
    return rows


if __name__ == "__main__":
    run(fast=True)
