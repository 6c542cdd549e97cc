"""Model-stack campaign benchmark: LM training + decode under crashes.

The first BENCH_* series for a *model* workload (the HPC suite has
``BENCH_campaign.json``): for each of ``lm-train`` and ``decode`` —

* the full §5.3 workflow (S1–S4 rates, critical objects, knapsack plan) on
  the registry-built app;
* a validation campaign under the selected plan;
* measured persistence traffic: bytes written per flush in ``delta`` mode
  (the ``delta_snapshot`` kernel path) vs ``full`` whole-object rewrites,
  over a short production-style run of :class:`EasyCrashManager`;
* the derived flush overhead ``t_s`` (:func:`persist_overhead_fraction`)
  and the system-efficiency gain it buys at the default 12 h-MTBF system.

Outputs ``benchmarks/results/model_campaign.csv`` and the repo-root
``BENCH_model.json``.

``--smoke`` runs a seconds-scale lm-train campaign only (the fast CI gate's
model smoke): asserts the S1–S4 partition and plan validity, writes nothing.
"""
from __future__ import annotations

import json
import os

from .common import Timer, campaign_size, campaign_workers, emit

MODEL_APPS = ("lm-train", "decode")

BENCH_JSON = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", "BENCH_model.json")
)


def _persist_traffic(app, n_steps: int = 6):
    """Measured flush bytes per step in delta vs full mode, plus step time."""
    import numpy as np

    from repro.core.arena import NVMArena
    from repro.core.manager import EasyCrashManager, FlushPolicy

    out = {}
    for mode in ("delta", "full"):
        arena = NVMArena(block_bytes=64)
        mgr = EasyCrashManager(
            arena,
            FlushPolicy(leaves=tuple(app.candidates), persist_mode=mode),
        )
        s = app.init(0)
        dt = 0.0
        for step in range(1, n_steps + 1):
            with Timer() as t:
                s = app.run_iteration(s)
            dt += t.dt
            mgr.maybe_flush(step, {k: np.asarray(v) for k, v in s.items()})
        # the arena keeps no files, so the traffic is its dirty blocks
        out[mode] = mgr.stats.blocks_written * arena.block_bytes / n_steps
        out["step_time"] = dt / n_steps
    return out


def run(fast: bool = True) -> None:
    from repro.core import CrashTester, efficiency_with, efficiency_without
    from repro.core.efficiency import SystemConfig, persist_overhead_fraction
    from repro.core.workflow import WorkflowConfig, run_workflow
    from repro.hpc.suite import bench_app, ci_app, default_cache

    n = max(16, campaign_size(fast) // 3)
    workers = campaign_workers()
    system = SystemConfig(mtbf=12 * 3600.0, t_chk=320.0)
    rows = []
    for name in MODEL_APPS:
        with Timer() as t:
            app = ci_app(name) if fast else bench_app(name)
            cache = default_cache(app)
            wf = run_workflow(app, WorkflowConfig(
                n_tests=n, cache=cache, seed=0, n_workers=workers,
                system=system,
            ))
            validated = CrashTester(app, wf.plan, cache, seed=777).run_campaign(
                n, n_workers=workers
            )
        traffic = _persist_traffic(app)
        t_s_delta = persist_overhead_fraction(traffic["delta"], traffic["step_time"])
        base_fr = wf.baseline_campaign.class_fractions()
        eff0 = efficiency_without(system).efficiency
        eff1 = efficiency_with(
            system, validated.recomputability, t_s=t_s_delta
        ).efficiency
        rows.append({
            "app": name,
            "S1_base": round(base_fr["S1"], 3),
            "S2_base": round(base_fr["S2"], 3),
            "S3_base": round(base_fr["S3"], 3),
            "S4_base": round(base_fr["S4"], 3),
            "recomp_easycrash": round(validated.recomputability, 3),
            "critical_objects": "|".join(wf.critical),
            "bytes_per_flush_full": int(traffic["full"]),
            "bytes_per_flush_delta": int(traffic["delta"]),
            "delta_ratio": round(traffic["delta"] / max(traffic["full"], 1), 3),
            "t_s_delta": round(t_s_delta, 6),
            "efficiency_gain_pts": round(100 * (eff1 - eff0), 2),
            "seconds": round(t.dt, 1),
        })
    emit(rows, "model_campaign")

    payload = {
        "config": {"fast": bool(fast), "n_tests": n, "seed": 0,
                   "system": {"mtbf": system.mtbf, "t_chk": system.t_chk}},
        "results": [
            {k: r[k] for k in ("app", "recomp_easycrash", "delta_ratio",
                               "t_s_delta", "efficiency_gain_pts")}
            for r in rows
        ],
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[model_campaign] wrote {BENCH_JSON}")


def smoke() -> None:
    """Seconds-scale lm-train campaign for the fast CI gate."""
    from repro.core import CrashTester, PersistPlan
    from repro.hpc.suite import default_cache, get_app

    app = get_app("lm-train", n_iters=6, batch=2, seq=8, width=32)
    cache = default_cache(app)
    camp = CrashTester(app, PersistPlan.none(), cache, seed=0).run_campaign(6)
    fr = camp.class_fractions()
    assert abs(sum(fr.values()) - 1.0) < 1e-9, fr
    assert len(camp.records) == 6
    ec = CrashTester(
        app, PersistPlan.at_loop_end(("params",), app), cache, seed=0
    ).run_campaign(6)
    assert ec.recomputability >= camp.recomputability
    print(f"[smoke] lm-train campaign ok: base {fr} -> "
          f"persist-params R={ec.recomputability:.2f}")


if __name__ == "__main__":
    import sys

    if "--smoke" in sys.argv:
        smoke()
    else:
        run(fast="--full" not in sys.argv)
