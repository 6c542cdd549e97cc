"""Fleet serving under failures: goodput / SLO / tail latency per policy.

The ROADMAP's "millions of users" leg of the paper's efficiency claim: N
decode replicas serve a diurnal open-loop request trace while each replica
fails per its trace and recovers via the policy under test.  The EasyCrash
policies draw recovery outcomes from a crash-campaign-*measured*
:class:`~repro.core.sysim.RecomputeProfile` of the ``decode`` app (PR 6's
registry model app) and pay a *measured* delta-flush overhead
(dirty blocks, ``ManagerStats.blocks_written``, through
:func:`~repro.core.efficiency.persist_overhead_fraction`) against their
serving rate; checkpoint policies pause serving for ``t_chk`` at the
Young/stretched-Young interval and come back *cold* (every interrupted
session re-runs prefill), while NVM recoveries warm-start with their KV
caches intact.

Writes ``benchmarks/results/fleetsim.csv``, the policy-frontier JSON
``benchmarks/results/fleet_frontier.json``, and the repo-root
``BENCH_fleet.json``, asserting the acceptance claims in-bench: the hybrid
policy dominates checkpoint-only on goodput *and* p99 at paper-like failure
rates, and seeded runs are byte-identical across repeats.

CLI:
  python -m benchmarks.bench_fleetsim            # fast (CI-sized) fleet
  python -m benchmarks.bench_fleetsim --full     # paper-sized campaign + 6 h tape
  python -m benchmarks.bench_fleetsim --smoke    # synthetic profile, seconds-scale
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

from .common import RESULTS_DIR, campaign_size, campaign_workers, emit

BENCH_JSON = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", "BENCH_fleet.json")
)
FRONTIER_PATH = os.path.join(RESULTS_DIR, "fleet_frontier.json")

SEED = 2024
#: per-node MTBF 12 h (the paper's machine class); one serving replica spans
#: a 48-node shard group, so its failure trace is the node trace scaled down
PER_NODE_MTBF = 12 * 3600.0
NODES_PER_REPLICA = 48

_PROFILE_CACHE: Dict[bool, Tuple[object, object, float]] = {}


def decode_profile(fast: bool = True):
    """Campaign-measure the ``decode`` app: its RecomputeProfile (S1–S4 +
    extra-iteration histogram) and its delta-mode flush overhead ``t_s``
    (bytes written per step / NVM bandwidth / step time)."""
    import numpy as np

    from repro.core import CrashTester, PersistPlan, RecomputeProfile
    from repro.core.arena import NVMArena
    from repro.core.efficiency import persist_overhead_fraction
    from repro.core.manager import EasyCrashManager, FlushPolicy
    from repro.hpc.suite import bench_app, ci_app, default_cache

    if fast in _PROFILE_CACHE:
        return _PROFILE_CACHE[fast]
    app = ci_app("decode") if fast else bench_app("decode")
    plan = PersistPlan.at_loop_end(app.candidates, app)
    camp = CrashTester(app, plan, default_cache(app), seed=SEED).run_campaign(
        max(16, campaign_size(fast) // 3), n_workers=campaign_workers()
    )
    profile = RecomputeProfile.from_campaign(camp)

    # measured persist traffic: delta-mode bytes per decode step
    import time

    arena = NVMArena(block_bytes=64)
    mgr = EasyCrashManager(arena, FlushPolicy(leaves=tuple(app.candidates)))
    s = app.init(0)
    n_steps, dt = 6, 0.0
    for step in range(1, n_steps + 1):
        t0 = time.perf_counter()
        s = app.run_iteration(s)
        dt += time.perf_counter() - t0
        mgr.maybe_flush(step, {k: np.asarray(v) for k, v in s.items()})
    # the arena keeps no files, so the traffic is its dirty blocks
    t_s = persist_overhead_fraction(
        mgr.stats.blocks_written * arena.block_bytes / n_steps, max(dt / n_steps, 1e-6)
    )
    _PROFILE_CACHE[fast] = (app, profile, t_s)
    return _PROFILE_CACHE[fast]


def fleet_config(fast: bool, t_s: float):
    """The benchmark fleet: diurnal traffic at ~0.85 utilization, paper-like
    per-replica failure rates, serving-scale checkpoints."""
    from repro.core import (
        ArrivalProcess,
        FleetConfig,
        PoissonTrace,
        ServiceModel,
        SystemConfig,
        scaled_trace,
    )

    trace = scaled_trace(PoissonTrace(PER_NODE_MTBF), 1, NODES_PER_REPLICA)
    return FleetConfig(
        n_replicas=4,
        arrival=ArrivalProcess(rate=6.8, amplitude=0.3),
        service=ServiceModel(mean_s=0.5, sigma=0.6, prefill_s=1.5),
        trace=trace,
        system=SystemConfig(mtbf=trace.mtbf, t_chk=30.0, nvm_restore_time=2.0),
        slo_latency=2.0,
        queue_cap=48,
        horizon=(2 if fast else 6) * 3600.0,
        t_s=t_s,
        t_iter=0.05,
        seed=SEED,
    )


def run(fast: bool = True):
    from repro.core import POLICIES, fleet_frontier

    app, profile, t_s = decode_profile(fast)
    cfg = fleet_config(fast, t_s)
    print(f"[fleet] decode profile: S1-S4 {dict(profile.fractions)} "
          f"(n={profile.n_records}), measured t_s={t_s:.4f}")
    print(f"[fleet] {cfg.n_replicas} replicas, mtbf={cfg.trace.mtbf:.0f}s/replica, "
          f"rate={cfg.arrival.rate}rps, horizon={cfg.horizon/3600:.0f}h")

    doc = fleet_frontier(cfg, profile)
    rows = []
    for policy in POLICIES:
        p = doc["policies"][policy]
        rows.append({
            "policy": policy,
            "goodput": round(p["goodput"], 4),
            "offered": round(p["offered_rate"], 4),
            "loss_frac": round(p["dropped"] / max(p["arrived"], 1), 4),
            "slo_frac": round(p["slo_violation_frac"], 4),
            "p50_s": round(p["latency_p50"], 3),
            "p95_s": round(p["latency_p95"], 3),
            "p99_s": round(p["latency_p99"], 3),
            "availability": round(p["availability"], 4),
            "n_failures": p["n_failures"],
            "n_nvm": p["n_nvm_recoveries"],
            "n_fallbacks": p["n_fallbacks"],
        })
    emit(rows, "fleetsim")

    # acceptance: seeded determinism is byte-identical across repeats
    again = fleet_frontier(cfg, profile)
    assert json.dumps(doc, sort_keys=True) == json.dumps(again, sort_keys=True), \
        "fleet simulation must be byte-identical for the same seed"
    # acceptance: hybrid dominates checkpoint-only on goodput and p99
    hyb, chk = doc["policies"]["hybrid"], doc["policies"]["checkpoint"]
    assert hyb["goodput"] > chk["goodput"], (
        f"hybrid goodput {hyb['goodput']:.4f} <= checkpoint {chk['goodput']:.4f}")
    assert hyb["latency_p99"] < chk["latency_p99"], (
        f"hybrid p99 {hyb['latency_p99']:.2f}s >= checkpoint "
        f"{chk['latency_p99']:.2f}s")
    print(f"[fleet] hybrid vs checkpoint: goodput {hyb['goodput']:.3f} > "
          f"{chk['goodput']:.3f} rps, p99 {hyb['latency_p99']:.2f} < "
          f"{chk['latency_p99']:.2f} s")

    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(FRONTIER_PATH, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[fleet] frontier -> {FRONTIER_PATH}")
    payload = {
        "config": {"fast": bool(fast), "fingerprint": doc["fingerprint"],
                   "app": app.name, "t_s": round(t_s, 6),
                   "mtbf_per_replica": cfg.trace.mtbf,
                   "seed": SEED},
        "profile": doc["profile"],
        "results": rows,
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[fleet] wrote {BENCH_JSON}")
    return rows


def smoke() -> None:
    """Seconds-scale synthetic-profile fleet for the CI fast gate: all four
    policies, conservation + determinism asserted, nothing written."""
    from repro.core import POLICIES, RecomputeProfile, simulate_fleet

    prof = RecomputeProfile.from_fractions(
        "smoke", {"S1": 0.7, "S2": 0.2, "S3": 0.05, "S4": 0.05},
        extra_iters_hist=((2, 3), (8, 1)),
    )
    cfg = fleet_config(fast=True, t_s=0.01).replace(horizon=900.0)
    for policy in POLICIES:
        p = prof if policy in ("easycrash", "hybrid") else None
        r = simulate_fleet(policy, cfg, p)
        again = simulate_fleet(policy, cfg, p)
        assert r == again, f"{policy}: same seed must reproduce bit-for-bit"
        assert r.arrived == r.served + r.dropped + r.in_flight, (policy, r)
        assert abs(sum(r.breakdown.values())
                   - cfg.n_replicas * cfg.horizon) < 1e-6, (policy, r.breakdown)
        print(f"[smoke] {policy:10s} goodput={r.goodput:.3f} "
              f"slo={r.slo_violation_frac:.3f} p99={r.latency_p99:.2f}s "
              f"fails={r.n_failures} nvm={r.n_nvm_recoveries}")
    print("[smoke] ok")


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="synthetic profile, seconds-scale fleet (CI gate)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    run(fast=not args.full)


if __name__ == "__main__":
    main()
