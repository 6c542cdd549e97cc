"""Differential + property suite for the vectorized campaign hot path.

The ``"vec"`` engine (SoA window simulator + batched lane recompute + shared
trace cache) must be bit-for-bit the ``"ref"`` oracle: identical
:class:`WindowTrace` output, identical resolved NVM images under tearing,
identical S1–S4 classification — per fault model, per worker count, and
through the cross-campaign trace cache.
"""
import numpy as np
import pytest

from repro.core import CrashTester, PersistPlan
from repro.core.cache_sim import (
    CacheConfig,
    Flush,
    RegionEvents,
    Sweep,
    resolve_window_images,
    simulate_window,
    simulate_window_vec,
)
from repro.core.crash_tester import records_match as _records_equal
from repro.core.faults import FAULT_MODELS, get_fault_model
from repro.core.trace_cache import WindowTraceCache
from repro.hpc.suite import ci_app, default_cache


def _small_app(name="sor"):
    if name == "sor":
        return ci_app("sor", grid=16, n_iters=60)
    return ci_app("pagerank", n_nodes=96, n_iters=60)


#: sub-CI sizes for the fast per-app differentials — every suite app that
#: opted into batched recompute + the jit-resident lane driver
TINY_SIZES = {
    "cg": dict(grid=12, n_iters=60),
    "mg": dict(grid=16, n_iters=8),
    "kmeans": dict(n_points=200, n_iters=6),
    "montecarlo": dict(batch=256, n_iters=8),
    "heat": dict(grid=16, n_iters=60),
    "pagerank": dict(n_nodes=96, n_iters=60),
}

#: the field advance_lanes carries that a perturbation meaningfully reaches
DRIVER_NOISE_FIELD = {
    "cg": "x", "mg": "u", "kmeans": "centroids",
    "montecarlo": "sums", "heat": "u", "pagerank": "rank",
}


def _tiny_app(name):
    return ci_app(name, **TINY_SIZES[name])


def _campaign(app, engine, fault=None, n_tests=8, workers=1, plan=None, tc=None):
    tester = CrashTester(
        app, plan if plan is not None else PersistPlan.none(),
        default_cache(app), seed=123, fault=fault, engine=engine,
        trace_cache=tc if tc is not None else WindowTraceCache(0, 0),
    )
    return tester.run_campaign(n_tests, n_workers=workers)


def _assert_traces_equal(a, b):
    assert a.obj_blocks == b.obj_blocks
    assert a.t_end == b.t_end
    assert a.eviction_writes == b.eviction_writes
    assert a.flush_writes == b.flush_writes
    assert a.flushed_clean_blocks == b.flushed_clean_blocks
    assert a.flush_ops == b.flush_ops
    assert a.spans == b.spans
    assert [(s.t_start, s.obj, s.seq, s.n_blocks) for s in a.sweeps] == [
        (s.t_start, s.obj, s.seq, s.n_blocks) for s in b.sweeps
    ]
    for o in a.obj_blocks:
        np.testing.assert_array_equal(a.wb_t[o], b.wb_t[o], err_msg=f"wb_t[{o}]")
        np.testing.assert_array_equal(a.wb_block[o], b.wb_block[o], err_msg=f"wb_block[{o}]")
        np.testing.assert_array_equal(a.wb_seq[o], b.wb_seq[o], err_msg=f"wb_seq[{o}]")


# ------------------------------------------------------ engine differentials
@pytest.mark.parametrize("fault_name", sorted(FAULT_MODELS))
def test_engines_identical_per_fault_model(fault_name):
    """Full-campaign record equality, ref vs vec, under every fault model
    (tearing, SDC, recovery crashes, biased crash points)."""
    results = {}
    for engine in ("ref", "vec"):
        app = _small_app("sor")
        fault = get_fault_model(fault_name, app=app)
        results[engine] = _campaign(app, engine, fault=fault, n_tests=8)
    assert _records_equal(results["ref"].records, results["vec"].records)
    assert results["ref"].class_fractions() == results["vec"].class_fractions()


def test_engines_identical_pagerank():
    """pagerank exercises hot-sweep windows and the lax.map batched spmv."""
    ref = _campaign(_small_app("pagerank"), "ref", n_tests=8)
    vec = _campaign(_small_app("pagerank"), "vec", n_tests=8)
    assert _records_equal(ref.records, vec.records)


@pytest.mark.parametrize("name", sorted(set(TINY_SIZES) - {"pagerank"}))
def test_engines_identical_newly_batched(name):
    """Full-campaign record equality, ref vs vec, on every app that gained
    batched recompute + the lane driver in this round (kmeans was the
    anti-case; cg/mg are the FMA-sensitive recurrences; montecarlo mixes
    eager and jit rounding in one serial app)."""
    ref = _campaign(_tiny_app(name), "ref", n_tests=6)
    vec = _campaign(_tiny_app(name), "vec", n_tests=6)
    assert _records_equal(ref.records, vec.records)
    assert ref.class_fractions() == vec.class_fractions()


@pytest.mark.parametrize("name", ["heat", "cg"])
def test_engines_identical_under_bitflip(name):
    """Silent bit flips can push restart lanes into blow-up territory, so
    this exercises the driver's suspect-lane path (non-finite residual →
    serial reclassification → S3) against the oracle."""
    results = {}
    for engine in ("ref", "vec"):
        app = _tiny_app(name)
        fault = get_fault_model("bit-flip", app=app)
        results[engine] = _campaign(app, engine, fault=fault, n_tests=6)
    assert _records_equal(results["ref"].records, results["vec"].records)


def _serial_advance(app, s0, it, stop):
    """The campaign's phase-A loop: step, then converged(), to the budget."""
    s = {k: np.array(v, copy=True) for k, v in s0.items()}
    while it < stop:
        s = app.run_iteration(s)
        it += 1
        try:
            if app.converged(s, it):
                break
        except FloatingPointError:
            return s, it, False
    return s, it, True


@pytest.mark.parametrize("name", sorted(TINY_SIZES))
def test_lane_driver_matches_serial_bitwise(name):
    """advance_lanes == the serial phase-A loop, full state bitwise, for
    lanes entering at scattered iterations (including at and near the
    stop bound) with small per-lane perturbations."""
    app = _tiny_app(name)
    noise_field = DRIVER_NOISE_FIELD[name]
    s = app.init(0)
    traj = [s]
    golden_iters = app.n_iters
    it = 0
    while it < app.n_iters:
        s = app.run_iteration(s)
        it += 1
        traj.append(s)
        if app.converged(s, it):
            golden_iters = it
            break
    rng = np.random.default_rng(7)
    entry_its = sorted({1, golden_iters // 2, max(golden_iters - 1, 1), golden_iters})
    lanes = []
    for ei in entry_its:
        lane = {k: np.array(v, copy=True) for k, v in traj[ei].items()}
        lane[noise_field] = (
            lane[noise_field]
            + rng.standard_normal(lane[noise_field].shape) * 1e-5
        ).astype(lane[noise_field].dtype)
        lanes.append((lane, ei))
    serial = [_serial_advance(app, s0, ei, golden_iters) for s0, ei in lanes]
    states, its, oks = app.advance_lanes(
        [s0 for s0, _ in lanes], [ei for _, ei in lanes], golden_iters
    )
    for i, ((ss, sit, sok), ds, dit, ok) in enumerate(zip(serial, states, its, oks)):
        if not sok:
            assert not ok, f"{name} lane {i}: driver missed a raising lane"
            continue
        assert bool(ok), f"{name} lane {i}: driver flagged a clean lane"
        assert int(dit) == sit, f"{name} lane {i}: stopped at {dit} != {sit}"
        for f in ss:
            a, b = np.asarray(ss[f]), np.asarray(ds[f])
            assert a.dtype == b.dtype and a.shape == b.shape, (name, i, f)
            assert np.array_equal(a.view(np.uint8), b.view(np.uint8)), (
                f"{name} lane {i}: field {f!r} not bitwise the serial value"
            )


#: poisoning this field reaches the convergence decision within one step,
#: so the serial loop raises FloatingPointError (cg decides on the carried
#: rho = r·r, not on x)
_POISON_FIELD = {"heat": "u", "cg": "r", "mg": "u", "pagerank": "rank"}


@pytest.mark.parametrize("name", ["heat", "cg", "mg", "pagerank"])
def test_lane_driver_flags_nan_lanes(name):
    """A NaN-poisoned lane (where serial converged() raises) must come back
    ok=False and untouched, while its healthy neighbours advance normally."""
    app = _tiny_app(name)
    noise_field = _POISON_FIELD[name]
    clean = app.init(0)
    clean = app.run_iteration(clean)
    poisoned = {k: np.array(v, copy=True) for k, v in clean.items()}
    poisoned[noise_field] = np.full_like(poisoned[noise_field], np.nan)
    stop = min(app.n_iters, 6)
    states, its, oks = app.advance_lanes([clean, poisoned], [1, 1], stop)
    assert bool(oks[0]) and not bool(oks[1])
    want, wit, wok = _serial_advance(app, clean, 1, stop)
    assert wok and int(its[0]) == wit
    for f in want:
        np.testing.assert_array_equal(
            np.asarray(want[f]).view(np.uint8),
            np.asarray(states[0][f]).view(np.uint8), err_msg=f,
        )


def test_lane_batch_invariance():
    """Campaign results are identical at any lane-batch setting — it is an
    execution-strategy knob, not a semantic one."""
    base = None
    for lb in (None, 1, 3):
        app = _tiny_app("kmeans")
        tester = CrashTester(
            app, PersistPlan.none(), default_cache(app), seed=123,
            engine="vec", trace_cache=WindowTraceCache(0, 0), lane_batch=lb,
        )
        camp = tester.run_campaign(6)
        if base is None:
            base = camp
        else:
            assert _records_equal(base.records, camp.records), lb


def test_engines_identical_with_flush_plan():
    """Flush events (plan-driven CLWB) through both engines."""
    results = {}
    for engine in ("ref", "vec"):
        app = _small_app("sor")
        plan = PersistPlan.at_loop_end(("u",), app)
        results[engine] = _campaign(app, engine, plan=plan, n_tests=8)
    assert _records_equal(results["ref"].records, results["vec"].records)


def test_window_traces_and_images_identical_on_app_windows():
    """WindowTrace fields and resolved NVM images (with torn blocks) are
    identical between engines on real application windows."""
    testers = {}
    for engine in ("ref", "vec"):
        app = _small_app("pagerank")
        testers[engine] = CrashTester(
            app, PersistPlan.at_loop_end(("rank",), app), default_cache(app),
            seed=7, engine=engine, trace_cache=WindowTraceCache(0, 0),
        )
        testers[engine]._ensure_golden()
    for crash_iter in (0, 3):
        tr_ref, sv_ref, ss_ref = testers["ref"]._simulate_crash_window(crash_iter)
        tr_vec, sv_vec, ss_vec = testers["vec"]._simulate_crash_window(crash_iter)
        _assert_traces_equal(tr_ref, tr_vec)
        assert ss_ref == ss_vec
        start = {
            o: testers["ref"]._golden_states[max(0, crash_iter - 1)][o]
            for o in ("rank", "y")
        }
        crash_ts = [ss_ref, ss_ref + 3, tr_ref.t_end - 1]
        fault = get_fault_model("torn-write", app=testers["ref"].app)
        for engine, tr, sv in (("ref", tr_ref, sv_ref), ("vec", tr_vec, sv_vec)):
            from repro.core.crash_tester import PlannedTest

            tearing = [
                fault.torn_blocks(PlannedTest(0, crash_iter, ct, fault_seed=99), tr, 64)
                for ct in crash_ts
            ]
            nvms, lives = resolve_window_images(
                tr, crash_ts, start, sv, 64, tearing=tearing
            )
            if engine == "ref":
                want_nvms, want_lives = nvms, lives
            else:
                for a, b in zip(want_nvms, nvms):
                    for o in a:
                        np.testing.assert_array_equal(a[o], b[o])
                for a, b in zip(want_lives, lives):
                    for o in a:
                        np.testing.assert_array_equal(a[o], b[o])


@pytest.mark.slow
@pytest.mark.parametrize("workers", [2, 4])
@pytest.mark.parametrize("name", ["sor", "kmeans"])
def test_vec_engine_worker_parity(name, workers):
    """vec-engine campaigns are identical at every worker count — and to the
    single-process ref engine.  kmeans rides the jit-resident lane driver,
    so this also proves the driver cache rebuilds identically in workers."""
    app = _tiny_app("kmeans") if name == "kmeans" else _small_app("sor")
    baseline = _campaign(app, "ref", n_tests=10, workers=1)
    app2 = _tiny_app("kmeans") if name == "kmeans" else _small_app("sor")
    fanned = _campaign(app2, "vec", n_tests=10, workers=workers)
    assert _records_equal(baseline.records, fanned.records)


def test_run_shards_matches_per_window(monkeypatch):
    """Cross-window chunked batching (run_shards) == per-shard execution,
    even when the chunk size forces mid-campaign flushes."""
    monkeypatch.setenv("REPRO_LANE_BATCH", "3")
    app = _small_app("sor")
    tester = CrashTester(
        app, PersistPlan.none(), default_cache(app), seed=123,
        engine="vec", trace_cache=WindowTraceCache(0, 0),
    )
    tests, shards = tester.plan_shards(10)
    seen = []
    chunked = tester.run_shards(shards, on_shard=lambda ci, recs: seen.append(ci))
    assert sorted(seen) == sorted(shards)
    per_window = {ci: tester.run_window_tests(ci, ts) for ci, ts in shards.items()}
    assert set(chunked) == set(per_window)
    for ci in per_window:
        assert [i for i, _ in chunked[ci]] == [i for i, _ in per_window[ci]]
        assert _records_equal(
            [r for _, r in chunked[ci]], [r for _, r in per_window[ci]]
        )


# ---------------------------------------------------------- trace-cache reuse
def test_trace_cache_cross_campaign_reuse():
    """A second campaign over the same app/plan hits the shared cache and
    still produces identical records (replay / robustness-matrix case)."""
    app = _small_app("sor")
    tc = WindowTraceCache()
    cold = _campaign(app, "vec", n_tests=8, tc=tc)
    assert tc.stats()["misses"] > 0
    before = tc.stats()["hits"]
    warm = _campaign(app, "vec", n_tests=8, tc=tc)
    assert _records_equal(cold.records, warm.records)
    assert tc.stats()["hits"] > before
    assert tc.stats()["misses"] == tc.stats()["traces"]  # no new simulations


def test_trace_cache_payloads_shared_across_plans():
    """Campaigns with different persist plans share window *payloads* (the
    app-side region re-execution) while keeping distinct traces."""
    app = _small_app("sor")
    tc = WindowTraceCache()
    base = _campaign(app, "vec", n_tests=8, tc=tc)
    stats0 = tc.stats()
    flush = _campaign(
        app, "vec", n_tests=8, tc=tc, plan=PersistPlan.at_loop_end(("u",), app)
    )
    stats1 = tc.stats()
    # same seed => same windows => every payload re-used, no payload misses
    assert stats1["payload_misses"] == stats0["payload_misses"]
    assert stats1["payload_hits"] > stats0["payload_hits"]
    # ...but the flush schedule differs, so traces were simulated anew
    assert stats1["traces"] > stats0["traces"]
    assert base.records != flush.records  # flushing u actually changes outcomes


def test_trace_cache_isolated_between_engines():
    """ref and vec testers sharing one cache never exchange traces (the
    engine is part of the trace key), so differential tests stay honest."""
    app = _small_app("sor")
    tc = WindowTraceCache()
    ref = _campaign(app, "ref", n_tests=6, tc=tc)
    hits_after_ref = tc.stats()["hits"]
    vec = _campaign(app, "vec", n_tests=6, tc=tc)
    assert _records_equal(ref.records, vec.records)
    # vec may reuse payloads but must not reuse ref's traces
    assert tc.stats()["hits"] == hits_after_ref


# ------------------------------------------------------- hypothesis property
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


def _random_window(rng):
    sizes = [int(rng.integers(1, 20)) for _ in range(int(rng.integers(1, 5)))]
    objs = {f"o{i}": s for i, s in enumerate(sizes)}
    names = list(objs)
    hot_obj = (
        min(names, key=lambda o: objs[o])
        if len(names) > 1 and rng.random() < 0.7 else None
    )
    regions = []
    seq_values = {}
    seq = 0
    for it in range(2):
        for ridx in range(int(rng.integers(1, 4))):
            events = []
            writes = []
            for _ in range(int(rng.integers(1, 5))):
                o = names[int(rng.integers(0, len(names)))]
                kind = int(rng.integers(0, 3))
                if kind == 2:
                    events.append(Flush(o))
                else:
                    hot = (
                        (hot_obj,)
                        if kind and hot_obj and o != hot_obj and rng.random() < 0.6
                        else ()
                    )
                    events.append(
                        Sweep(o, write=bool(kind), hot=hot,
                              hot_every=int(rng.integers(2, 8)))
                    )
                    if kind:
                        writes.append(o)
            regions.append(
                RegionEvents(seq=seq, iter_idx=it, region_idx=ridx, events=tuple(events))
            )
            seq_values[seq] = {
                o: rng.standard_normal(objs[o] * 16).astype(np.float32)
                for o in set(writes)
            }
            seq += 1
    start = {
        o: rng.standard_normal(objs[o] * 16).astype(np.float32) for o in names
    }
    capacity = int(rng.integers(1, sum(sizes) + 5))
    return CacheConfig(capacity, 64), objs, regions, start, seq_values


if HAVE_HYPOTHESIS:

    @given(
        tol=st.floats(1e-8, 1e-1, allow_nan=False, allow_infinity=False),
        raw=st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=300, deadline=None)
    def test_f32_monotone_cutoff_property(tol, raw):
        """The lane driver replaces each app's host-side float64 threshold
        predicate with an exact float32 compare against a bisected cutoff:
        for every finite non-negative f32 value v, ``v <= cutoff`` must equal
        the original predicate ``pred(float(v))`` — otherwise an in-jit
        convergence decision could diverge from the serial loop by one
        iteration and break bit-for-bit equality."""
        from repro.core.lane_driver import f32_monotone_cutoff

        v = np.int32(raw).view(np.float32)
        if not np.isfinite(v) or v < 0:
            return
        pred = lambda x: x < tol * 0.5  # noqa: E731 - the serial decision shape
        cutoff = f32_monotone_cutoff(pred)
        assert bool(v <= cutoff) == bool(pred(float(v)))

    @given(seed=st.integers(0, 100_000))
    @settings(max_examples=60, deadline=None)
    def test_vec_simulator_matches_oracle_property(seed):
        """simulate_window_vec == simulate_window on arbitrary event windows
        (sweeps, flushes, hot re-reads, adversarial capacities), including
        the images the batch resolver derives from the trace."""
        rng = np.random.default_rng(seed)
        cfg, objs, regions, start, seq_values = _random_window(rng)
        ref = simulate_window(cfg, objs, regions)
        vec = simulate_window_vec(cfg, objs, regions)
        _assert_traces_equal(ref, vec)
        if ref.t_end == 0:
            return
        crash_ts = rng.integers(0, ref.t_end + 1, size=4).tolist()
        # block_bytes=64 but values are 16 floats per block: pass the
        # geometry the generator used
        ref_imgs = resolve_window_images(ref, crash_ts, start, seq_values, 64)
        vec_imgs = resolve_window_images(vec, crash_ts, start, seq_values, 64)
        for side in (0, 1):
            for a, b in zip(ref_imgs[side], vec_imgs[side]):
                for o in a:
                    np.testing.assert_array_equal(a[o], b[o])
