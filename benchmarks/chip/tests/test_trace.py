"""The trace reduction, on a small trace recorded on the CPU."""
import jax
import jax.numpy as jnp
import pytest

import xplane


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host"):
                sum(range(200_000))
    jax.profiler.stop_trace()
    return xplane.load(str(d), device_prefix=None)


def test_window_and_busy_time(cpu_trace):
    tr = cpu_trace
    assert tr.window_s > 0
    busy = tr.busy_s()
    assert 0 < busy <= tr.window_s


def test_program_time_is_found_by_name(cpu_trace):
    secs, events = cpu_trace.module_seconds(lambda name: name.startswith("jit__lambda"))
    assert events >= 3 and 0 < secs <= cpu_trace.busy_s() + 1e-12
    none, zero = cpu_trace.module_seconds(lambda name: "no_such_program" in name)
    assert none == 0 and zero == 0


def test_breakdown_lists(cpu_trace):
    top = cpu_trace.top_ops(10)
    assert 0 < len(top) <= 10
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    assert any(name.startswith("jit__lambda/") for name, _ in top)
    gaps = cpu_trace.idle_gaps(10, {"bench.step", "bench.host"})
    assert 0 < len(gaps) <= 10
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))
    assert gaps[0][0] == "bench.host"
    assert sum(g for _, g in gaps) <= cpu_trace.window_s - cpu_trace.busy_s() + 1e-9


def test_device_planes_are_read_from_their_op_line(cpu_trace, tmp_path):
    # a CPU trace has no device plane: reading it as a TPU trace finds no
    # operations, and the readers then report nothing rather than 0
    d = tmp_path / "t"
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    tr = xplane.load(str(d))
    assert tr.ops == {} and tr.busy_s() == 0.0 and tr.idle_gaps() == []
