"""The reader of the program's own spans and the metrics built on it, on a
small trace recorded on the CPU."""
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import program_spans
import xplane
from modules import load_module

CHIP = Path(__file__).resolve().parents[1]
NEW_METRICS = ("host_copy_ms_per_step.serve", "decode_ms_per_step.serve",
               "mask_ms_per_step.serve", "arena_write_ms_per_step.serve",
               "write_amplification.serve")
K, V = "cache/group0/pos0/k", "cache/group0/pos0/v"
STEPS = 4


def metric(name):
    return load_module(CHIP / "metrics" / f"{name}.py", "test_metric_" + name.replace(".", "_"))


def _record(d: Path, window: bool = True, inside: bool = True) -> None:
    """Two decode steps and one flush of a K and a V object inside the
    window (``inside``), a decode step and a flush before it with stats that
    must not count."""
    ann = jax.profiler.TraceAnnotation
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128), jnp.float32)
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)

    def flush(kv_bytes, dirty):
        with ann("flush", step=8, mode="delta"):
            for obj in (K, V):
                with ann("flush.mask", object=obj, nbytes=kv_bytes, block_bytes=64) as m:
                    m.set_metadata(blocks=kv_bytes // 64, dirty_blocks=dirty)
                with ann("arena.write", object=obj, nbytes=kv_bytes + 128):
                    pass
                with ann("arena.fsync", object=obj):
                    pass
            with ann("flush.mask", object="tokens", nbytes=64, block_bytes=64,
                     blocks=1, dirty_blocks=1):
                pass
            with ann("arena.write", object="tokens", nbytes=192):
                pass

    with ann("serve.decode", step=0):
        pass
    flush(6400, 100)  # before the window: not counted
    if window:
        with ann(xplane.WINDOW_SPAN):
            step(x).block_until_ready()
            if inside:
                with ann("serve.session", session="abc", prompts=2):
                    for i in range(2):
                        with ann("serve.decode", step=i + 1):
                            step(x).block_until_ready()
                        with ann("serve.host_copy", nbytes=1000):
                            time.sleep(0.1)  # the longest idle stretch
                    flush(6400, 25)
    jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    work = tmp_path_factory.mktemp("work")
    _record(work / "trace")
    return work


def _ctx(work, steps=STEPS, trace=True):
    xtrace = xplane.load(str(work / "trace"), device_prefix=None) if trace else None
    return SimpleNamespace(workdir=work, counters={"decode_steps": steps}, xtrace=xtrace)


def test_only_the_programs_spans_inside_the_window_are_kept(trace_dir):
    spans = program_spans.spans(_ctx(trace_dir))
    names = [s.name for s in spans]
    assert names.count("serve.decode") == 2 and names.count("flush") == 1
    assert names.count("flush.mask") == 3 and names.count("arena.write") == 3
    assert xplane.WINDOW_SPAN not in names and names.count("serve.session") == 1
    assert {s.stats["step"] for s in spans if s.name == "serve.decode"} == {1, 2}
    session = next(s for s in spans if s.name == "serve.session")
    assert session.stats == {"session": "abc", "prompts": 2}
    assert all(session.start_ns <= s.start_ns and s.end_ns <= session.end_ns
               and s.thread == session.thread for s in spans)


def test_spans_are_read_once_per_run(trace_dir):
    ctx = _ctx(trace_dir)
    first = program_spans.spans(ctx)
    assert program_spans.spans(ctx) is first


def test_stats_and_seconds_are_summed_per_name(trace_dir):
    ctx = _ctx(trace_dir)
    assert program_spans.stat_sum(ctx, "serve.host_copy", "nbytes") == 2000
    assert program_spans.stat_sum(ctx, "arena.write", "nbytes") == 2 * 6528 + 192
    assert program_spans.stat_sum(ctx, "arena.write", "nbytes",
                                  lambda s: s.stats["object"] == K) == 6528
    assert program_spans.stat_sum(ctx, "flush.mask", "dirty_blocks") == 51
    assert program_spans.stat_sum(ctx, "arena.write", "no_such_stat") is None
    host = program_spans.seconds(ctx, "serve.host_copy")
    both = program_spans.seconds(ctx, "serve.host_copy", "serve.decode")
    assert 0 < host < both
    assert program_spans.ms_per_step(ctx, "serve.host_copy") == pytest.approx(
        1000 * host / STEPS)
    assert program_spans.seconds(ctx, "arena.mix") is None


def test_metrics_read_the_window(trace_dir):
    ctx = _ctx(trace_dir)
    values = {name: metric(name).read(ctx) for name in NEW_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    # K and V: 6400 + 128 bytes each reached the files, 25 blocks each were dirty
    assert values["write_amplification.serve"] == pytest.approx(6528 / (25 * 64))
    assert values["arena_write_ms_per_step.serve"] == pytest.approx(
        1000 * program_spans.seconds(ctx, "arena.write", "arena.fsync") / STEPS)


@pytest.fixture(scope="module")
def without_spans(tmp_path_factory):
    """Work directories whose trace has a window without the program's
    spans, no window, and no trace at all."""
    bare, nowindow = tmp_path_factory.mktemp("bare"), tmp_path_factory.mktemp("nowindow")
    _record(bare / "trace", inside=False)
    _record(nowindow / "trace", window=False)
    return [bare, nowindow, nowindow / "no_such_dir"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metrics_are_absent_without_their_spans(name, trace_dir, without_spans):
    read = metric(name).read
    assert read(_ctx(trace_dir, trace=False)) is not None
    for work in without_spans:
        assert read(_ctx(work, trace=False)) is None
    if name.startswith(("host_copy", "decode", "mask", "arena")):
        assert read(_ctx(trace_dir, steps=0, trace=False)) is None


def test_idle_time_is_named_by_the_innermost_program_span(trace_dir):
    ctx = _ctx(trace_dir)
    share = program_spans.idle_covered_share(ctx)
    assert 0 < share <= 1
    gaps = program_spans.idle_gaps(ctx, 3)
    assert 0 < len(gaps) <= 3 and all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))
    assert gaps[0][0] == "serve.host_copy"
    assert program_spans.idle_covered_share(_ctx(trace_dir, trace=False)) is None
