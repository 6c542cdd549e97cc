"""The program's spans and counters in the profiler's trace: one small decode
session with delta flushes, traced, against the same session untraced and
one with whole-object flushes."""
import glob
import io
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch
from repro.core import NVMArena
from repro.core.manager import EasyCrashManager, FlushPolicy
from repro.launch import serve
from repro.models import scaled_down
from repro.telemetry import span, tracing

WIDTH, PROMPTS, PROMPT_LEN, STEPS, EVERY = 64, 2, 8, 12, 4
FLUSHES = STEPS // EVERY
KV = ("cache/group0/pos0/k", "cache/group0/pos0/v")
OBJECTS = (*KV, "cache/t", "tokens", "__step__")
DEVICE = (*KV, "cache/t")


def _serve(workdir, mode="delta", every=EVERY):
    return serve.main(["--width", str(WIDTH), "--prompts", str(PROMPTS),
                       "--prompt-len", str(PROMPT_LEN), "--decode-steps", str(STEPS),
                       "--flush-every", str(every), "--persist-mode", mode,
                       "--workdir", str(workdir)])


def _read_spans(trace_dir):
    """(name, thread, start_ns, end_ns, stats) of every annotation on the host."""
    pb = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(pb[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, line.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def sessions(tmp_path_factory):
    root = tmp_path_factory.mktemp("telemetry")
    out = {"untraced": _serve(root / "untraced")}
    jax.profiler.start_trace(str(root / "trace"))
    out["traced"] = _serve(root / "traced")
    jax.profiler.stop_trace()
    out["full"] = _serve(root / "full", mode="full")
    jax.profiler.start_trace(str(root / "trace_nopersist"))
    _serve(root / "nopersist", every=10 ** 9)
    jax.profiler.stop_trace()
    out["spans"] = _read_spans(str(root / "trace"))
    out["nopersist_spans"] = _read_spans(str(root / "trace_nopersist"))
    out["root"] = root
    return out


def _named(sessions, name):
    return [s for s in sessions["spans"] if s[0] == name]


def _npy_size(shape, dtype):
    f = io.BytesIO()
    np.save(f, np.zeros(shape, dtype))
    return f.tell()


def _files_received(arena_dir):
    """Per object, the size of its file at each flush: fixed-size objects
    are rewritten whole at every flush (every one has a dirty block), the
    token buffer grows by ``EVERY`` tokens a row between flushes."""
    out = {}
    for name in OBJECTS:
        path = os.path.join(arena_dir, name.replace("/", "__") + ".npy")
        if name == "tokens":
            out[name] = [_npy_size((PROMPTS, PROMPT_LEN + 1 + EVERY * k), np.int32)
                         for k in range(1, FLUSHES + 1)]
            assert out[name][-1] == os.path.getsize(path)
        else:
            out[name] = [os.path.getsize(path)] * FLUSHES
    return out


@pytest.mark.parametrize("name,count", [
    ("serve.session", 1), ("serve.setup", 1), ("serve.prefill", 1),
    # a copy for each flush, and the served token buffer at the end
    ("serve.decode", STEPS), ("serve.host_copy", FLUSHES + 1),
    ("flush", FLUSHES), ("flush.stage", FLUSHES),
    ("flush.mask", FLUSHES * len(OBJECTS)), ("arena.write", FLUSHES * len(OBJECTS)),
    # the cache's objects on the device, K, V and the position: fetched whole
    # at the first flush, their dirty blocks at each later one
    ("flush.fetch", FLUSHES * len(DEVICE)),
    ("arena.fsync", FLUSHES * len(OBJECTS)), ("arena.rename", FLUSHES * len(OBJECTS)),
    ("arena.manifest", FLUSHES),
    # later flushes mix the dirty blocks in; the growing token buffer is
    # rewritten whole
    ("arena.mix", (FLUSHES - 1) * (len(OBJECTS) - 1)),
])
def test_each_span_occurs_once_per_piece_of_work(sessions, name, count):
    assert len(_named(sessions, name)) == count


def test_each_merge_counts_the_blocks_its_mask_marked(sessions):
    masks = [(m[4]["object"], m[4]["dirty_blocks"]) for m in _named(sessions, "flush.mask")
             if m[4]["object"] != "tokens"]
    # the first flush writes every object whole; the growing token buffer
    # is rewritten whole at every flush
    assert [(m[4]["object"], m[4]["blocks"]) for m in _named(sessions, "arena.mix")] == (
        masks[len(OBJECTS) - 1:])


def test_spans_carry_their_stats(sessions):
    (s,) = _named(sessions, "serve.session")
    assert s[4]["prompts"] == PROMPTS and s[4]["decode_steps"] == STEPS
    assert s[4]["resumed"] == 0 and len(s[4]["session"]) == 32
    assert [d[4]["step"] for d in _named(sessions, "serve.decode")] == list(range(1, STEPS + 1))
    assert [f[4] for f in _named(sessions, "flush")] == [
        {"step": EVERY * k, "mode": "delta"} for k in range(1, FLUSHES + 1)]
    # the server copies the token buffer alone to the host: the flush fetches
    # what it writes of the cache (flush.fetch)
    copies = [c[4]["nbytes"] for c in _named(sessions, "serve.host_copy")]
    assert copies == [_token_bytes(EVERY * k) for k in range(1, FLUSHES + 1)] + [
        _token_bytes(STEPS)]


def _token_bytes(steps):
    return 4 * PROMPTS * (PROMPT_LEN + 1 + steps)


def test_a_session_without_flushes_copies_only_the_served_tokens(sessions):
    spans = sessions["nopersist_spans"]
    assert [s[4] for s in spans if s[0] == "serve.host_copy"] == [
        {"nbytes": _token_bytes(STEPS)}]
    assert sum(s[0] == "serve.decode" for s in spans) == STEPS
    assert not any(s[0].startswith(("flush", "arena.")) for s in spans)


@pytest.mark.parametrize("obj", OBJECTS)
def test_write_counter_is_what_the_file_received(sessions, obj):
    arena = sessions["root"] / "traced" / "serve_arena"
    written = [w[4]["nbytes"] for w in _named(sessions, "arena.write")
               if w[4]["object"] == obj]
    assert written == _files_received(arena)[obj]


@pytest.mark.parametrize("inner,outer", [
    ("arena.write", "flush"), ("flush.mask", "flush"), ("flush", "serve.session"),
    ("flush.fetch", "flush.mask"),
    ("serve.decode", "serve.session"), ("serve.host_copy", "serve.session"),
])
def test_spans_nest_on_their_thread(sessions, inner, outer):
    parents = _named(sessions, outer)
    for _, thread, start, end, _ in _named(sessions, inner):
        assert any(t == thread and s <= start and end <= e for _, t, s, e, _ in parents)


def _cfg():
    return scaled_down(get_arch("stablelm-1.6b"), width=WIDTH)


@pytest.fixture(scope="module")
def hybrid_spans(tmp_path_factory):
    """A traced delta session of the hybrid: its recurrent state is written
    whole at every flush, its K/V masked."""
    root = tmp_path_factory.mktemp("telemetry_hybrid")
    jax.profiler.start_trace(str(root / "trace"))
    serve.main(["--arch", "granite-4.0-h-micro", "--width", str(WIDTH),
                "--prompts", str(PROMPTS), "--prompt-len", str(PROMPT_LEN),
                "--decode-steps", str(STEPS), "--flush-every", str(EVERY),
                "--workdir", str(root / "run")])
    jax.profiler.stop_trace()
    return _read_spans(str(root / "trace"))


def _fetches(spans):
    """Each ``flush.fetch`` with the ``flush.mask`` or ``flush.whole`` span
    of the same object around it on its thread (None where there is none)."""
    flushes = [s for s in spans if s[0] in ("flush.mask", "flush.whole")]
    return [(f, next((o for o in flushes if o[1] == f[1] and o[2] <= f[2] and f[3] <= o[3]
                      and o[4]["object"] == f[4]["object"]), None))
            for f in spans if f[0] == "flush.fetch"]


@pytest.fixture(params=["stablelm", "granite"])
def traced(request, sessions, hybrid_spans):
    return sessions["spans"] if request.param == "stablelm" else hybrid_spans


def test_each_fetch_lies_in_the_flush_span_of_its_object(traced):
    fetches = _fetches(traced)
    assert fetches and all(outer is not None for _, outer in fetches)
    # every flush.whole of a device object holds one fetch, whole
    whole = [s for s in traced if s[0] == "flush.whole"]
    assert sorted(o[2] for _, o in fetches if o[0] == "flush.whole") == sorted(
        s[2] for s in whole)
    assert all(f[4]["whole"] == 1 for f, o in fetches if o[0] == "flush.whole")


def _pow2(n):
    return 0 if n == 0 else 1 << (n - 1).bit_length()


def test_a_fetch_counts_the_bytes_it_brought(traced):
    """Whole: the object's bytes. Masked: the rows that hold the dirty
    blocks, each the fewest blocks that fill 128 lanes of bytes, their count
    rounded up to a power of two (at most every row), and the mask, a byte a
    block."""
    for f, outer in _fetches(traced):
        stats = outer[4]
        if f[4]["whole"]:
            assert f[4]["nbytes"] == stats["nbytes"]
            assert outer[0] == "flush.whole" or stats["dirty_blocks"] == stats["blocks"]
            continue
        dirty, blocks = stats["dirty_blocks"], stats["blocks"]
        row = 128  # the device masks and gathers an object's bytes
        k, rows = row // stats["block_bytes"], f[4]["rows"]
        n_rows = -(-blocks // k)
        assert f[4]["nbytes"] == rows * row + blocks
        # between the fewest and the most rows the dirty blocks can lie in
        assert min(_pow2(-(-dirty // k)), n_rows) <= rows <= min(_pow2(dirty), n_rows)
        assert rows == n_rows or rows == _pow2(rows)
    # the hybrid's K/V are masked at its later flushes, its state never
    assert any(not f[4]["whole"] for f, _ in _fetches(traced))


def test_write_amplification_follows_from_the_shapes(sessions):
    cfg = _cfg()
    length = PROMPT_LEN + STEPS + 1
    row = cfg.n_kv_heads * cfg.head_dim * 2          # bytes of one position, bf16
    assert row % 64 == 0                             # positions fill whole blocks
    kv_bytes = cfg.n_layers * PROMPTS * length * row
    header = os.path.getsize(sessions["root"] / "traced" / "serve_arena"
                             / "cache__group0__pos0__k.npy") - kv_bytes
    # the first flush finds all of each object dirty; each later one the
    # EVERY positions decoded since, in every layer and row
    dirty = kv_bytes + (FLUSHES - 1) * cfg.n_layers * PROMPTS * EVERY * row
    expected = FLUSHES * (kv_bytes + header) / dirty

    def kv(s):
        return s[4]["object"] in KV
    written = sum(w[4]["nbytes"] for w in _named(sessions, "arena.write") if kv(w))
    marked = sum(m[4]["dirty_blocks"] * m[4]["block_bytes"]
                 for m in _named(sessions, "flush.mask") if kv(m))
    assert marked == 2 * dirty
    assert written / marked == pytest.approx(expected, rel=1e-12)


def test_tracing_changes_nothing_served_or_persisted(sessions):
    assert np.array_equal(sessions["traced"]["tokens"], sessions["untraced"]["tokens"])
    traced = sessions["root"] / "traced" / "serve_arena"
    untraced = sessions["root"] / "untraced" / "serve_arena"
    files = sorted(os.listdir(traced))
    assert files == sorted(os.listdir(untraced)) and len(files) == len(OBJECTS) + 1
    for f in files:
        assert (traced / f).read_bytes() == (untraced / f).read_bytes(), f


@pytest.mark.parametrize("run", ["traced", "untraced", "full"])
def test_bytes_written_is_what_the_files_received(sessions, run):
    arena = sessions["root"] / run / "serve_arena"
    assert sessions[run]["bytes_written"] == sum(
        sum(sizes) for sizes in _files_received(arena).values())


@pytest.mark.parametrize("mode", ["delta", "full"])
def test_an_unchanged_object_reaches_its_file_only_in_full_mode(tmp_path, mode):
    arena = NVMArena(backing_dir=str(tmp_path))
    mgr = EasyCrashManager(arena, FlushPolicy(leaves=("a", "b"), persist_mode=mode))
    a, b = np.arange(1024, dtype=np.float32), np.zeros(256, np.float32)
    mgr.maybe_flush(1, {"a": a, "b": b})
    first = mgr.stats.bytes_written
    sizes = {n: os.path.getsize(tmp_path / f"{n}.npy") for n in ("a", "b", "__step__")}
    assert first == sum(sizes.values())
    b = b.copy()
    b[3] = 1.0  # "a" unchanged, one block of "b" dirty
    mgr.maybe_flush(2, {"a": a, "b": b})
    again = sizes["b"] + sizes["__step__"] + (sizes["a"] if mode == "full" else 0)
    assert mgr.stats.bytes_written - first == again
    assert mgr.stats.blocks_written - (64 + 16 + 1) == (64 + 16 + 1 if mode == "full" else 2)


def test_a_span_records_only_while_a_profiler_traces(tmp_path):
    assert not tracing()
    with span("outside", a=1) as s:
        s.add(b=2)
    jax.profiler.start_trace(str(tmp_path))
    assert tracing()
    with span("inside", a=1) as s:
        s.add(b="two")
    jax.profiler.stop_trace()
    assert not tracing()
    found = {n: stats for n, _, _, _, stats in _read_spans(str(tmp_path))}
    assert found["inside"] == {"a": 1, "b": "two"} and "outside" not in found
