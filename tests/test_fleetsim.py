"""Fleet serving-under-failure simulator: conservation laws, seeded
determinism, limit-case reductions, and the differential against the
single-job ``sysim`` oracle.

The fleet simulator is a seeded DES, so its invariants have exact oracles:
every request is served, dropped, or in flight — never lost to bookkeeping;
replica-seconds partition exactly into up/checkpoint/down; identical seeds
reproduce byte-identical results; and with one replica and no traffic the
availability accounting must reduce to ``sysim``'s single-job work fraction.
"""
import dataclasses
import json

import pytest

from repro.core.efficiency import SystemConfig
from repro.core.fleetsim import (
    ArrivalProcess,
    FleetConfig,
    FleetResult,
    ServiceModel,
    fleet_frontier,
    simulate_fleet,
)
from repro.core.sysim import (
    POLICIES,
    PoissonTrace,
    RecomputeProfile,
    WeibullTrace,
    simulate_policy,
)

PROFILE = RecomputeProfile.from_fractions(
    "decode", {"S1": 0.75, "S2": 0.15, "S3": 0.05, "S4": 0.05},
    extra_iters_hist=((2, 4), (9, 1)),
)

SERVE_SYS = SystemConfig(mtbf=1800.0, t_chk=20.0, nvm_restore_time=2.0)


def _cfg(**over) -> FleetConfig:
    base = dict(
        n_replicas=3,
        arrival=ArrivalProcess(rate=3.0, amplitude=0.25),
        service=ServiceModel(mean_s=0.4, sigma=0.5, prefill_s=0.8),
        trace=PoissonTrace(mtbf=600.0),
        system=SERVE_SYS,
        slo_latency=1.5,
        queue_cap=32,
        horizon=1800.0,
        seed=0,
    )
    base.update(over)
    return FleetConfig(**base)


def _prof_for(policy):
    return PROFILE if policy in ("easycrash", "hybrid") else None


# ----------------------------------------------- invariants at fixed seeds
# (the hypothesis-driven generalizations live in
# tests/test_fleetsim_properties.py, which skips when hypothesis is absent)
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("seed", [0, 7, 1234])
def test_request_conservation_and_time_partition(policy, seed):
    """arrived == served + dropped + in-flight, exactly, for every policy;
    and replica-seconds partition into up/checkpoint/down."""
    cfg = _cfg(
        trace=PoissonTrace(mtbf=300.0),
        queue_cap=8,
        horizon=900.0,
        t_s=0.05,
        seed=seed,
    )
    r = simulate_fleet(policy, cfg, _prof_for(policy))
    assert r.arrived == r.served + r.dropped + r.in_flight
    assert r.dropped_down <= r.dropped
    assert sum(r.breakdown.values()) == pytest.approx(
        cfg.n_replicas * cfg.horizon, abs=1e-6
    )
    assert 0.0 <= r.availability <= 1.0
    assert 0.0 <= r.slo_violation_frac <= 1.0
    if r.served:
        assert r.latency_p50 <= r.latency_p95 <= r.latency_p99 <= r.latency_max


@pytest.mark.parametrize("policy", POLICIES)
def test_identical_seeds_are_byte_identical(policy):
    cfg = _cfg(seed=42, horizon=600.0)
    a = simulate_fleet(policy, cfg, _prof_for(policy))
    b = simulate_fleet(policy, cfg, _prof_for(policy))
    assert a == b
    assert json.dumps(a.payload(), sort_keys=True) == \
        json.dumps(b.payload(), sort_keys=True)
    assert dataclasses.asdict(a) == dataclasses.asdict(b)


def test_different_seed_changes_the_tape():
    a = simulate_fleet("hybrid", _cfg(seed=1), PROFILE)
    b = simulate_fleet("hybrid", _cfg(seed=2), PROFILE)
    assert a.arrived != b.arrived or a.latency_mean != b.latency_mean


# ------------------------------------------------- monotonicity + limit cases
@pytest.mark.parametrize("policy", POLICIES)
def test_goodput_monotone_as_failures_vanish(policy):
    """Failure rate -> 0 can only help: the offered tape is drawn from
    streams independent of the failure trace, so served counts at a quiet
    MTBF dominate served counts at a harsh one (checked across seeds with a
    harsh/quiet gap wide enough that the ordering is not a coin flip)."""
    for seed in (0, 1, 2):
        served = []
        for mtbf in (200.0, 2000.0, 1e12):
            cfg = _cfg(
                trace=PoissonTrace(mtbf=mtbf),
                arrival=ArrivalProcess(rate=4.0, amplitude=0.3),
                horizon=3600.0,
                seed=seed,
            )
            r = simulate_fleet(policy, cfg, _prof_for(policy))
            served.append(r.served)
        assert served[0] <= served[1] <= served[2], (policy, seed, served)


def test_no_failures_no_recoveries():
    cfg = _cfg(trace=PoissonTrace(mtbf=1e15), horizon=1200.0)
    r = simulate_fleet("hybrid", cfg, PROFILE)
    assert r.n_failures == 0
    assert r.n_nvm_recoveries == r.n_fallbacks == r.n_cold_restarts == 0
    assert r.dropped_down == 0
    # quiet fleet: hybrid still checkpoints on its stretched interval
    assert r.breakdown.get("down", 0.0) == 0.0


def test_offered_load_is_trace_invariant():
    """The same seed offers the same request tape no matter the failure
    trace or policy — the property the policy frontier depends on."""
    base = simulate_fleet("none", _cfg(trace=PoissonTrace(1e12)))
    for policy in POLICIES:
        for mtbf in (300.0, 3000.0):
            r = simulate_fleet(policy, _cfg(trace=PoissonTrace(mtbf)),
                               _prof_for(policy))
            assert r.arrived == base.arrived


def test_zero_rate_serves_nothing():
    r = simulate_fleet("checkpoint", _cfg(arrival=ArrivalProcess(rate=0.0)))
    assert r.arrived == r.served == r.dropped == r.in_flight == 0
    assert r.latency_p99 == 0.0  # strict-JSON-safe sentinel, not NaN
    assert r.n_checkpoints > 0   # idle replicas still checkpoint on schedule


def test_warm_beats_cold_recovery_on_tail_latency():
    """The KV-cache story in one assertion: a perfect NVM profile (always
    warm) yields a better tail than the same fleet restoring cold, because
    cold recovery re-runs prefill for every interrupted session."""
    warm_prof = RecomputeProfile.from_fractions("p", {"S1": 1.0})
    cfg = _cfg(
        trace=PoissonTrace(mtbf=240.0),
        arrival=ArrivalProcess(rate=4.5, amplitude=0.0),
        service=ServiceModel(mean_s=0.4, sigma=0.5, prefill_s=3.0),
        horizon=3600.0,
        seed=5,
    )
    warm = simulate_fleet("easycrash", cfg, warm_prof)
    cold = simulate_fleet("checkpoint", cfg)
    assert warm.n_nvm_recoveries > 0
    assert warm.latency_p99 < cold.latency_p99
    assert warm.goodput >= cold.goodput


# ------------------------------------------------------- reduction to sysim
@pytest.mark.slow
@pytest.mark.parametrize("policy", POLICIES)
def test_reduction_to_sysim_availability(policy):
    """One replica, no traffic: the fleet's availability must match the
    single-job simulator's work-time fraction for every policy (same trace
    distribution, same recovery semantics, independent RNG streams — so the
    comparison is statistical, over ~2000 failure events)."""
    horizon = 120 * 24 * 3600.0
    system = SystemConfig(mtbf=3600.0, t_chk=60.0, nvm_restore_time=5.0)
    prof = _prof_for(policy)
    cfg = FleetConfig(
        n_replicas=1,
        arrival=ArrivalProcess(rate=0.0),
        trace=PoissonTrace(mtbf=3600.0),
        system=system,
        horizon=horizon,
        t_iter=1.0,
        seed=3,
    )
    fleet = simulate_fleet(policy, cfg, prof)
    job = simulate_policy(policy, system, PoissonTrace(3600.0), prof,
                          n_failures=0, horizon=horizon, t_iter=1.0, seed=3)
    job_work_frac = job.breakdown.get("work", 0.0) / job.total_time
    assert fleet.availability == pytest.approx(job_work_frac, abs=0.02), (
        policy, fleet.availability, job_work_frac
    )
    # both sides actually saw a failure-rich tape
    assert fleet.n_failures > 1000 and job.n_failures > 1000


# ------------------------------------------------------------- config + API
def test_config_validation():
    with pytest.raises(ValueError, match="n_replicas"):
        _cfg(n_replicas=0)
    with pytest.raises(ValueError, match="rate"):
        ArrivalProcess(rate=-1.0)
    with pytest.raises(ValueError, match="amplitude"):
        ArrivalProcess(rate=1.0, amplitude=1.0)
    with pytest.raises(ValueError, match="mean_s"):
        ServiceModel(mean_s=0.0)
    with pytest.raises(ValueError, match="t_s"):
        _cfg(t_s=1.0)
    with pytest.raises(ValueError, match="queue_cap"):
        _cfg(queue_cap=0)
    with pytest.raises(ValueError, match="unknown policy"):
        simulate_fleet("raid", _cfg())
    with pytest.raises(ValueError, match="RecomputeProfile"):
        simulate_fleet("hybrid", _cfg())


def test_config_spec_fingerprint_round_trip():
    """spec() is JSON-round-trip safe and the fingerprint is stable under
    round-trip but sensitive to any identity field (mirrors WorkflowConfig)."""
    cfg = _cfg(trace=WeibullTrace(mtbf=900.0, shape=0.7))
    spec = json.loads(json.dumps(cfg.spec()))
    assert spec == cfg.spec()
    assert cfg.fingerprint() == cfg.replace().fingerprint()
    assert cfg.fingerprint() != cfg.replace(seed=cfg.seed + 1).fingerprint()
    assert cfg.fingerprint() != cfg.replace(n_replicas=5).fingerprint()
    # a field-for-field rebuild of the same values fingerprints identically
    rebuilt = FleetConfig(
        **{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    )
    assert cfg.fingerprint() == rebuilt.fingerprint()


def test_diurnal_modulation_shapes_the_offered_load():
    """With amplitude > 0 and the period matched to the horizon, the peak
    half of the tape must carry more arrivals than the trough half."""
    cfg = _cfg(
        arrival=ArrivalProcess(rate=3.0, amplitude=0.8, period=3600.0),
        trace=PoissonTrace(1e12),
        horizon=3600.0,
        seed=9,
    )
    # first half of the sine period is the peak (sin >= 0), second the trough
    rng_probe = ArrivalProcess(rate=3.0, amplitude=0.8, period=3600.0)
    assert rng_probe.rate_at(900.0) > rng_probe.rate_at(2700.0)
    r = simulate_fleet("none", cfg)
    assert r.arrived > 0
    assert r.offered_rate == pytest.approx(r.arrived / cfg.horizon)


def test_frontier_document_is_strict_json():
    cfg = _cfg(horizon=600.0)
    doc = fleet_frontier(cfg, PROFILE)
    round_trip = json.loads(json.dumps(doc, allow_nan=False))
    assert set(round_trip["policies"]) == set(POLICIES)
    assert round_trip["fingerprint"] == cfg.fingerprint()
    for p in round_trip["policies"].values():
        assert p["arrived"] == p["served"] + p["dropped"] + p["in_flight"]


def test_result_is_frozen():
    r = simulate_fleet("none", _cfg(horizon=300.0))
    assert isinstance(r, FleetResult)
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.goodput = 1.0


def test_persist_tax_slows_easycrash_service():
    """t_s inflates EasyCrash service times (capacity charge): with a busy
    fleet and no failures, mean latency at t_s=0.3 exceeds t_s=0."""
    quiet = PoissonTrace(1e15)
    cfg0 = _cfg(trace=quiet, t_s=0.0, horizon=1200.0,
                arrival=ArrivalProcess(rate=5.0))
    cfg1 = cfg0.replace(t_s=0.3)
    r0 = simulate_fleet("easycrash", cfg0, PROFILE)
    r1 = simulate_fleet("easycrash", cfg1, PROFILE)
    assert r1.latency_mean > r0.latency_mean
    # ...and the tax never applies to the checkpoint policy
    c0 = simulate_fleet("checkpoint", cfg0)
    c1 = simulate_fleet("checkpoint", cfg1)
    assert c0 == c1


def test_tiny_rate_arrival_overflows_to_never():
    """A rate so small that the exponential draw overflows means no arrival,
    not a math domain error in the diurnal rate (the conservation property's
    hypothesis search drew such a rate)."""
    import math

    import numpy as np

    from repro.core.fleetsim import ArrivalProcess

    arr = ArrivalProcess(rate=2.225073858507203e-309, amplitude=0.5)
    assert arr.next_arrival(np.random.default_rng(0), 0.0) == math.inf
