"""Guards that keep a device failure from passing as a result.

On an accelerator a campaign runs in one process (the chip belongs to it),
a failing batched program raises instead of quietly handing its lanes to
the host loop, and compiled programs land in one fixed cache directory.
The backend is steered with ``monkeypatch``; everything runs on the CPU.
"""
import jax
import pytest
from jax.errors import JaxRuntimeError

from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache
from repro.core import CrashTester, PersistPlan
from repro.core.crash_tester import campaign_executor
from repro.core.faults import MultiCrash
from repro.core.workflow import WorkflowConfig, run_workflow
from repro.hpc.suite import ci_app, default_cache


def _tiny(name="heat"):
    # montecarlo has no grid, and its CI size is already small
    app = ci_app(name) if name == "montecarlo" else ci_app(name, grid=12, n_iters=40)
    return app, default_cache(app)


# ------------------------------------------------------- one process per chip
@pytest.mark.parametrize("backend", ["tpu", "gpu"])
def test_executor_refuses_workers_off_cpu(backend, monkeypatch):
    app, cache = _tiny()
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match=f"n_workers=2.*{backend}"):
        campaign_executor(2, app, cache)
    campaign_executor(1, app, cache).shutdown()


def test_campaign_and_workflow_refuse_workers_off_cpu(monkeypatch):
    app, cache = _tiny()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="n_workers=1"):
        CrashTester(app, PersistPlan.none(), cache, seed=1).run_campaign(8, n_workers=2)
    with pytest.raises(RuntimeError, match="n_workers=1"):
        run_workflow(app, WorkflowConfig(n_tests=8, cache=cache, n_workers=2))


def test_executor_allows_workers_on_cpu():
    app, cache = _tiny()
    assert jax.default_backend() == "cpu"
    campaign_executor(2, app, cache).shutdown()


# ----------------------------------------------- no fallback past the device
def test_lane_driver_failure_raises(monkeypatch):
    app, cache = _tiny()

    def broken(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: lane bucket does not fit")

    monkeypatch.setattr(app, "advance_lanes", broken)
    tester = CrashTester(app, PersistPlan.none(), cache, seed=1, engine="vec")
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        tester.run_campaign(6)


def test_batched_step_failure_raises(monkeypatch):
    app, cache = _tiny("sor")  # sor steps lanes through run_iteration_batch

    def broken(states):
        raise RuntimeError("compile failed")

    monkeypatch.setattr(app, "run_iteration_batch", broken)
    tester = CrashTester(app, PersistPlan.none(), cache, seed=1, engine="vec")
    with pytest.raises(RuntimeError, match="compile failed"):
        tester.run_campaign(6)


def _device_error(*a, **k):
    raise JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory allocating lane buffers")


def _overflowing(states):
    raise OverflowError("lane counter out of range")


def test_batched_step_lane_arithmetic_error_stays_per_lane(monkeypatch):
    """A lane's own ArithmeticError is still attributed per lane: the batch
    falls back to serial steps and the records equal the ref oracle's."""
    from repro.core.crash_tester import records_match

    app, cache = _tiny("sor")
    ref = CrashTester(app, PersistPlan.none(), cache, seed=1, engine="ref").run_campaign(6)
    monkeypatch.setattr(app, "run_iteration_batch", _overflowing)
    vec = CrashTester(app, PersistPlan.none(), cache, seed=1, engine="vec").run_campaign(6)
    assert records_match(ref.records, vec.records)


# Each case breaks one app hook after the golden run, so the error strikes
# inside the recompute, where an app's own exception would classify as S3.
@pytest.mark.parametrize("engine", ["ref", "vec"])
@pytest.mark.parametrize("app_name,hook,setup,fault", [
    pytest.param("heat", "init", {}, None, id="restart"),
    pytest.param("montecarlo", "verify", {}, None, id="verify"),
    pytest.param("montecarlo", "converged", {"supports_lane_driver": False}, None,
                 id="converged"),
    pytest.param("sor", "run_iteration", {"run_iteration_batch": _overflowing}, None,
                 id="serial-step"),
    pytest.param("heat", "run_iteration", {}, MultiCrash(), id="recovery-crash"),
])
def test_device_error_in_recompute_raises(app_name, hook, setup, fault, engine, monkeypatch):
    """A device error is never a crash outcome: it stops the campaign on
    both engines instead of being recorded as S3."""
    app, cache = _tiny(app_name)
    tester = CrashTester(app, PersistPlan.none(), cache, seed=1, engine=engine, fault=fault)
    tester._ensure_golden()
    monkeypatch.setattr(app, hook, _device_error)
    for name, value in setup.items():
        monkeypatch.setattr(app, name, value)
    with pytest.raises(JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        tester.run_campaign(6)


# -------------------------------------------------------- compile cache
@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = DEFAULT_CACHE_DIR.parent
    assert DEFAULT_CACHE_DIR.name == ".jax_cache"
    assert (repo / "pyproject.toml").is_file() and (repo / "src" / "repro").is_dir()
    assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
