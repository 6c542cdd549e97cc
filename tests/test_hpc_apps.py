"""Every HPC app: golden run passes its own acceptance verification."""
import numpy as np
import pytest

from repro.hpc import app_names, get_app
from repro.hpc.suite import CI_SIZES, ci_app


@pytest.mark.parametrize("name", sorted(CI_SIZES))
def test_golden_verifies(name):
    app = ci_app(name)
    state, iters = app.run_golden()
    res = app.verify(state)
    assert res.passed, (name, res)
    assert iters > 0


@pytest.mark.parametrize("name", sorted(CI_SIZES))
def test_regions_declare_their_writes(name):
    """Region metadata must match behaviour: a region only mutates objects it
    declares in ``writes`` (the cache model depends on this)."""
    app = ci_app(name)
    state = app.init(0)
    # run one warm-up iteration so temporals are populated
    state = app.run_iteration(state)
    for region in app.regions():
        before = {k: np.array(v, copy=True) for k, v in state.items()}
        state = region.fn(state)
        for k in state:
            if k in region.writes:
                continue
            assert np.array_equal(before[k], state[k]), (
                f"{name}: region {region.name} mutated undeclared object {k}"
            )


@pytest.mark.parametrize("name", sorted(CI_SIZES))
def test_restart_init_installs_persisted(name):
    app = ci_app(name)
    state = app.init(0)
    state = app.run_iteration(state)
    persisted = {c: state[c] for c in app.candidates if c in state}
    restored = app.restart_init(0, persisted)
    for c, v in persisted.items():
        assert np.allclose(restored[c].astype(np.float64), np.asarray(v, np.float64)), (name, c)


@pytest.mark.parametrize("name", sorted(CI_SIZES))
def test_deterministic_iterations(name):
    """Redo of the same iteration from the same state must be bit-identical
    (the basis for trajectory-match acceptance)."""
    app = ci_app(name)
    s0 = app.init(0)
    s0 = app.run_iteration(s0)
    snap = {k: np.array(v, copy=True) for k, v in s0.items()}
    a = app.run_iteration({k: np.array(v, copy=True) for k, v in snap.items()})
    b = app.run_iteration({k: np.array(v, copy=True) for k, v in snap.items()})
    for k in a:
        assert np.array_equal(a[k], b[k]), (name, k)


def test_registry():
    assert set(app_names()) == set(CI_SIZES)
    with pytest.raises(KeyError):
        get_app("nope")


@pytest.mark.parametrize("n", [1, 3, 8, 1000, 2304])
def test_tree_sum_order_is_shape_independent(n):
    """tree_sum rounds the same for one vector, each row of a stack, and a
    vmapped stack — the property the batched lanes rely on — and sums to
    the exact total within float32 rounding."""
    import jax
    import jax.numpy as jnp

    from repro.hpc.common import tree_sum

    rng = np.random.default_rng(n)
    rows = rng.standard_normal((5, n)).astype(np.float32)
    one = np.array([np.asarray(jax.jit(tree_sum)(jnp.asarray(r))) for r in rows])
    stacked = np.asarray(jax.jit(tree_sum)(jnp.asarray(rows)))
    mapped = np.asarray(jax.jit(jax.vmap(tree_sum))(jnp.asarray(rows)))
    assert one.view(np.uint32).tolist() == stacked.view(np.uint32).tolist()
    assert one.view(np.uint32).tolist() == mapped.view(np.uint32).tolist()
    np.testing.assert_allclose(one, rows.astype(np.float64).sum(axis=1), rtol=1e-5, atol=1e-5)


def test_tree_sum_adds_halves():
    """The order is the documented one: zero-pad to a power of two, then add
    the upper half onto the lower — [a, b, c] sums as (a + c) + b."""
    import jax.numpy as jnp

    from repro.hpc.common import tree_sum

    x = jnp.asarray([1e8, 1.0, -1e8], jnp.float32)
    assert float(tree_sum(x)) == 1.0  # left to right it would be 0.0
