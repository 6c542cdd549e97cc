"""Property tests for the adaptive scheduler's statistical machinery.

Three families, matching the soundness claims in
:mod:`repro.core.adaptive`:

* Wilson intervals — coverage on synthetic Bernoulli streams, width
  monotonicity in ``n`` and ``z``, containment of the point estimate;
* the self-normalized importance-sampling estimator — exact agreement
  with the plain mean under uniform weights, convergence to the
  uniform-draw rates under a tilted proposal, Kish ``n_eff <= n``;
* the stopping rule — :func:`selection_invariant` NEVER returns a
  decision while any point inside the gain box would change the
  knapsack's plan (stopping cannot fire while the decision is
  interval-ambiguous).

Hypothesis is a dev-only dependency; the file skips cleanly where it is
not installed (the pinned differential suite in tests/test_adaptive.py
does not depend on it).
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.adaptive import (  # noqa: E402
    effective_sample_size,
    final_rate_interval,
    selection_invariant,
    weighted_outcome_stats,
    wilson_interval,
)
from repro.core.selection import select_regions_from_gains  # noqa: E402


# ------------------------------------------------------------------- Wilson
@given(
    n=st.integers(min_value=1, max_value=500),
    frac=st.floats(min_value=0.0, max_value=1.0),
    z=st.floats(min_value=0.1, max_value=4.0),
)
def test_wilson_contains_point_and_stays_in_unit_interval(n, frac, z):
    s = frac * n
    lo, hi = wilson_interval(s, n, z)
    assert 0.0 <= lo <= hi <= 1.0
    assert lo <= s / n + 1e-12 and s / n - 1e-12 <= hi


@given(
    n=st.integers(min_value=2, max_value=400),
    frac=st.floats(min_value=0.0, max_value=1.0),
)
def test_wilson_width_shrinks_with_n(n, frac):
    """Same success fraction, more samples -> never a wider interval."""
    lo1, hi1 = wilson_interval(frac * n, n)
    lo2, hi2 = wilson_interval(frac * 2 * n, 2 * n)
    assert (hi2 - lo2) <= (hi1 - lo1) + 1e-12


@given(
    n=st.integers(min_value=1, max_value=400),
    frac=st.floats(min_value=0.0, max_value=1.0),
    z=st.floats(min_value=0.2, max_value=2.0),
)
def test_wilson_width_grows_with_z(n, frac, z):
    lo1, hi1 = wilson_interval(frac * n, n, z)
    lo2, hi2 = wilson_interval(frac * n, n, z * 1.5)
    assert (hi2 - lo2) >= (hi1 - lo1) - 1e-12


def test_wilson_coverage_on_bernoulli_streams():
    """Empirical coverage within slack of nominal on synthetic streams."""
    rng = np.random.default_rng(7)
    for p in (0.1, 0.5, 0.9):
        for n in (20, 60):
            hits = 0
            trials = 1500
            for _ in range(trials):
                s = rng.binomial(n, p)
                lo, hi = wilson_interval(s, n, z=1.96)
                hits += lo <= p <= hi
            # nominal 95%; Wilson is near-nominal for all p, n
            assert hits / trials >= 0.92, (p, n, hits / trials)


# --------------------------------------------------------- IS estimator
@given(
    vals=st.lists(st.sampled_from([0.0, 1.0]), min_size=1, max_size=60),
    w=st.floats(min_value=0.05, max_value=20.0),
)
def test_uniform_weights_recover_plain_mean(vals, w):
    rate, n_eff = weighted_outcome_stats(vals, [w] * len(vals))
    assert rate == pytest.approx(float(np.mean(vals)))
    assert n_eff == pytest.approx(len(vals))


@given(
    weights=st.lists(st.floats(min_value=0.01, max_value=50.0),
                     min_size=1, max_size=60),
)
def test_kish_effective_sample_size_bounds(weights):
    n_eff = effective_sample_size(weights)
    assert 1.0 - 1e-9 <= n_eff <= len(weights) + 1e-9


def test_self_normalized_is_converges_to_uniform_rates():
    """Tilted proposal + p/q weights recover the uniform-draw S1 rate."""
    rng = np.random.default_rng(11)
    p = np.array([0.5, 0.3, 0.2])          # uniform (span-proportional) mass
    q = np.array([0.2, 0.3, 0.5])          # tilted proposal
    rates = np.array([1.0, 0.4, 0.1])      # per-region S1 probability
    true_rate = float(p @ rates)
    n = 6000
    ks = rng.choice(3, size=n, p=q)
    vals = (rng.random(n) < rates[ks]).astype(float)
    ws = (p / q)[ks]
    est, n_eff = weighted_outcome_stats(vals.tolist(), ws.tolist())
    assert est == pytest.approx(true_rate, abs=0.03)
    assert n_eff < n                       # non-uniform weights cost ESS


# ----------------------------------------------------------- stopping rule
@st.composite
def knapsack_instances(draw):
    n_regions = draw(st.integers(min_value=1, max_value=4))
    point, boxes, overheads = {}, {}, {}
    for k in range(n_regions):
        lo = draw(st.floats(min_value=-0.5, max_value=0.9))
        width = draw(st.floats(min_value=0.0, max_value=0.4))
        point[k] = lo + width * draw(st.floats(min_value=0.0, max_value=1.0))
        boxes[k] = (lo, lo + width)
        overheads[k] = draw(st.floats(min_value=1e-4, max_value=0.05))
    y_base = draw(st.floats(min_value=0.0, max_value=1.0))
    t_s = draw(st.floats(min_value=0.005, max_value=0.1))
    tau = draw(st.floats(min_value=0.1, max_value=0.9))
    return point, boxes, overheads, y_base, t_s, tau


@settings(max_examples=60, deadline=None)
@given(inst=knapsack_instances(), data=st.data())
def test_stopping_never_fires_while_decision_ambiguous(inst, data):
    """If selection_invariant claims a decision, every point inside the
    gain box (not just the corners) yields that same plan."""
    point, boxes, overheads, y_base, t_s, tau = inst
    decision = selection_invariant(point, boxes, overheads, y_base,
                                   t_s=t_s, tau=tau)
    if decision is None:
        return
    # the point estimate itself must produce the claimed plan
    assert select_regions_from_gains(
        point, overheads, y_base, t_s=t_s, tau=tau).plan_freqs() == decision
    # and so must arbitrary interior points of the box
    for _ in range(5):
        gains = {
            k: lo + (hi - lo) * data.draw(
                st.floats(min_value=0.0, max_value=1.0))
            for k, (lo, hi) in boxes.items()
        }
        assert select_regions_from_gains(
            gains, overheads, y_base, t_s=t_s, tau=tau,
        ).plan_freqs() == decision, gains


@pytest.mark.parametrize("point", [0.0, 3e-14])
def test_hair_thin_box_across_zero_gain_is_ambiguous(point):
    """A gain box of width 3e-14 from 0 up holds plans that skip the region
    (gain 0) and plans that take it (gain > 0): no decision, wherever the
    point estimate sits (hypothesis found both cases)."""
    assert selection_invariant({0: point}, {0: (0.0, 3e-14)}, {0: 0.03125},
                               0.0, t_s=0.0625, tau=0.5) is None


def test_max_corners_guard_never_claims_invariance():
    point = {k: 0.5 for k in range(3)}
    boxes = {k: (0.1, 0.9) for k in range(3)}
    overheads = {k: 0.001 for k in range(3)}
    assert selection_invariant(point, boxes, overheads, 0.2,
                               t_s=0.03, tau=0.4, max_corners=4) is None


# ------------------------------------------------------ final_rate_interval
@given(
    vals=st.lists(st.sampled_from([0.0, 1.0]), min_size=1, max_size=40),
    data=st.data(),
)
def test_final_rate_interval_invariants(vals, data):
    ws = [data.draw(st.floats(min_value=0.1, max_value=5.0))
          for _ in vals]
    rem = [data.draw(st.floats(min_value=0.1, max_value=5.0))
           for _ in range(data.draw(st.integers(min_value=0, max_value=20)))]
    lo, hi, rate, n_eff = final_rate_interval(vals, ws, rem, z=1.645)
    assert 0.0 <= lo <= rate <= hi <= 1.0
    # hard reachable bound is never violated
    s = float(np.dot(vals, ws))
    w_tot = float(np.sum(ws) + np.sum(rem))
    assert lo >= s / w_tot - 1e-9
    assert hi <= (s + float(np.sum(rem))) / w_tot + 1e-9
    if not rem:
        # no remaining mass: Wilson may stay wide but the hard bound (and
        # therefore the intersection) collapses onto the exact final rate
        assert lo == pytest.approx(rate) and hi == pytest.approx(rate)
