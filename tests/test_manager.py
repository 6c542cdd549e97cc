"""Production runtime: arena durability, flush/restore, checkpoint fallback."""
import json
import os

import numpy as np
import pytest

from repro.core import NVMArena
from repro.core.manager import EasyCrashManager, FlushPolicy, flatten_state, unflatten_state


def _state(step=0):
    return {
        "params": {"w": np.full((8, 8), float(step), np.float32),
                   "b": np.zeros(8, np.float32)},
        "opt": {"mu": np.ones(8, np.float32) * step},
        "step": np.asarray(step, np.int64),
    }


def test_flatten_roundtrip():
    s = _state(3)
    flat = flatten_state(s)
    assert set(flat) == {"params/w", "params/b", "opt/mu", "step"}
    back = unflatten_state(flat)
    assert np.array_equal(back["params"]["w"], s["params"]["w"])


def test_flush_and_restore(tmp_path):
    arena = NVMArena(backing_dir=str(tmp_path))
    policy = FlushPolicy(leaves=("params",), every_steps=1)
    mgr = EasyCrashManager(arena, policy)
    mgr.maybe_flush(5, _state(5))

    # simulate crash: new process reattaches to the arena
    arena2 = NVMArena.reattach(str(tmp_path))
    mgr2 = EasyCrashManager(arena2, policy)
    restored, step, source = mgr2.restore(_state(0))
    assert source == "easycrash"
    assert step == 5
    assert np.all(restored["params"]["w"] == 5.0)
    # opt state was NOT in the flush policy: restores from init
    assert np.all(restored["opt"]["mu"] == 0.0)


def test_delta_flush_counts_only_dirty(tmp_path):
    arena = NVMArena(backing_dir=str(tmp_path))
    policy = FlushPolicy(leaves=("params",), every_steps=1)
    mgr = EasyCrashManager(arena, policy)
    s = _state(1)
    mgr.maybe_flush(1, s)
    first = mgr.stats.blocks_written
    assert first == 4 + 1 + 1  # params/w, params/b and the step, whole
    mgr.maybe_flush(2, s)  # identical values: delta flush writes ~nothing
    # only the __step__ scalar changed
    assert mgr.stats.blocks_written - first == 1


def test_flush_cadence():
    arena = NVMArena()
    policy = FlushPolicy(leaves=("params",), every_steps=4)
    mgr = EasyCrashManager(arena, policy)
    issued = [mgr.maybe_flush(s, _state(s)) for s in range(8)]
    assert issued == [True, False, False, False, True, False, False, False]


@pytest.mark.parametrize("every", [1, 3, 8])
def test_due_is_where_maybe_flush_flushes(every):
    mgr = EasyCrashManager(NVMArena(), FlushPolicy(leaves=("params",), every_steps=every))
    due = [mgr.due(s) for s in range(25)]
    issued = [mgr.maybe_flush(s, _state(s)) for s in range(25)]
    assert due == issued
    assert mgr.stats.flushes_issued == sum(due) == len(range(0, 25, every))


def test_verify_hook_rejects_to_checkpoint(tmp_path):
    saved = {}

    def save(step, state):
        saved["step"] = step
        saved["state"] = state

    def restore():
        if not saved:
            return None
        return saved["step"], saved["state"]

    arena = NVMArena(backing_dir=str(tmp_path))
    policy = FlushPolicy(leaves=("params",), every_steps=1)
    mgr = EasyCrashManager(
        arena, policy, checkpoint_save=save, checkpoint_restore=restore,
        mtbf=3600.0, t_chk=10.0, recomputability=0.8, step_time=60.0,
    )
    assert mgr.checkpoint_every is not None
    save(3, _state(3))
    mgr.maybe_flush(7, _state(7))
    # acceptance verification rejects the arena image -> checkpoint fallback
    state, step, source = mgr.restore(_state(0), verify=lambda s, t: False)
    assert source == "checkpoint"
    assert step == 3
    assert mgr.stats.checkpoint_restores == 1


def test_young_checkpoint_interval_stretches_with_recomputability():
    arena = NVMArena()
    policy = FlushPolicy(leaves=("params",))
    low = EasyCrashManager(arena, policy, mtbf=3600.0, t_chk=10.0,
                           recomputability=0.0, step_time=1.0)
    high = EasyCrashManager(arena, policy, mtbf=3600.0, t_chk=10.0,
                            recomputability=0.9, step_time=1.0)
    assert high.checkpoint_every > low.checkpoint_every


# ------------------------------------------- objects a step rewrites whole
REWRITTEN = ("cache/group0/pos0",)  # a recurrent layer's state


def _hybrid_state(step):
    """A decode cache of one recurrent layer (state rewritten every step)
    and one attention layer (K/V gaining one position a step)."""
    rng = np.random.default_rng(step)
    kv = np.zeros((2, 3, 16, 4), np.float32)
    kv[:, :, :step] = np.arange(1, step + 1, dtype=np.float32)[None, None, :, None]
    return {
        "cache": {
            "t": np.asarray(step, np.int32),
            "group0": {
                "pos0": {"ssm": rng.standard_normal((2, 3, 4, 8)).astype(np.float32),
                         "conv": rng.standard_normal((2, 3, 3, 8)).astype(np.float16)},
                "pos1": {"k": kv, "v": -kv},
            },
        },
        "tokens": np.arange(3 * (4 + step), dtype=np.int32).reshape(3, -1),
    }


def _flush_steps(tmp_path, mode, rewritten, monkeypatch, steps=(1, 2, 3)):
    """Flush the hybrid state at ``steps``; returns the manager and, per
    flush, the masks computed: (object shape, dirty blocks)."""
    from repro.core import delta_persist

    masked = []
    real = delta_persist.delta_block_mask

    def record(cur, live, block_bytes=64):
        mask = real(cur, live, block_bytes)
        masked.append((live.shape, int(np.count_nonzero(mask))))
        return mask

    monkeypatch.setattr(delta_persist, "delta_block_mask", record)
    arena = NVMArena(backing_dir=str(tmp_path))
    policy = FlushPolicy(leaves=("cache", "tokens"), every_steps=1, persist_mode=mode)
    mgr = EasyCrashManager(arena, policy, rewritten=rewritten)
    calls = []
    for step in steps:
        before = len(masked)
        mgr.maybe_flush(step, _hybrid_state(step))
        calls.append(masked[before:])
    return mgr, calls


@pytest.mark.parametrize("mode", ["delta", "full"])
def test_whole_written_objects_open_no_mask(tmp_path, mode, monkeypatch):
    from repro.core.blocks import obj_num_blocks

    mgr, calls = _flush_steps(tmp_path, mode, REWRITTEN, monkeypatch)
    state = flatten_state(_hybrid_state(3))
    whole = {n for n in state if n.startswith(REWRITTEN[0] + "/")}
    assert whole == {"cache/group0/pos0/ssm", "cache/group0/pos0/conv"}
    # in delta mode, every other object of a later flush, and the step, is
    # masked; but the token buffer, which grows, and is written whole
    for step, call in zip((1, 2, 3), calls):
        flat = flatten_state(_hybrid_state(step))
        shapes = [] if mode == "full" or step == 1 else [
            str(flat[n].shape) for n in flat if n not in whole and n != "tokens"] + ["()"]
        assert sorted(str(shape) for shape, _ in call) == sorted(shapes)
    # each flush writes every block of the rewritten objects
    per_flush = sum(obj_num_blocks(state[n], 64) for n in whole)
    assert mgr.stats.blocks_written >= 3 * per_flush


def test_kv_keeps_its_delta_mask_beside_whole_written_state(tmp_path, monkeypatch):
    _, calls = _flush_steps(tmp_path, "delta", REWRITTEN, monkeypatch)
    kv_shape = (2, 3, 16, 4)
    # K and V: written whole at the first flush (no image yet), then masked:
    # a step adds one 16-byte position, one dirty 64-byte block in each of
    # the 2 x 3 (layer, row) slices
    assert [[dirty for shape, dirty in call if shape == kv_shape]
            for call in calls] == [[], [6] * 2, [6] * 2]


@pytest.mark.parametrize("mode", ["delta", "full"])
@pytest.mark.parametrize("rewritten", [REWRITTEN, ()], ids=["whole", "masked"])
def test_arena_images_are_the_flushed_state_byte_for_byte(tmp_path, mode, rewritten,
                                                          monkeypatch):
    _flush_steps(tmp_path, mode, rewritten, monkeypatch)
    want = flatten_state(_hybrid_state(3))
    arena = NVMArena.reattach(str(tmp_path))
    for name, live in want.items():
        img = arena.get(name)
        assert img.dtype == live.dtype and img.shape == live.shape, name
        assert img.tobytes() == live.tobytes(), name
    assert int(arena.get("__step__")) == 3


def test_a_state_of_attention_alone_flushes_as_before(tmp_path, monkeypatch):
    """A stablelm-shaped cache has nothing to write whole: its flushes ask
    for the same masks and write the same blocks and bytes as a manager
    told of no rewritten objects."""
    from repro.configs import get_arch
    from repro.models import rewritten_leaves

    assert rewritten_leaves(get_arch("stablelm-1.6b")) == ()
    assert len(rewritten_leaves(get_arch("granite-4.0-h-micro"))) == 9

    def attention_state(step):
        s = _hybrid_state(step)
        del s["cache"]["group0"]["pos0"]
        return s

    runs = []
    for i, rewritten in enumerate([(), tuple(f"cache/{leaf}" for leaf in
                                             rewritten_leaves(get_arch("stablelm-1.6b")))]):
        from repro.core import delta_persist

        masked = []
        real = delta_persist.delta_block_mask
        monkeypatch.setattr(delta_persist, "delta_block_mask",
                            lambda c, l, b=64, real=real: masked.append(l.shape)
                            or real(c, l, b))
        arena = NVMArena(backing_dir=str(tmp_path / str(i)))
        mgr = EasyCrashManager(arena, FlushPolicy(leaves=("cache", "tokens"), every_steps=1,
                                                  persist_mode="delta"),
                               rewritten=rewritten)
        for step in (1, 2, 3):
            mgr.maybe_flush(step, attention_state(step))
        monkeypatch.setattr(delta_persist, "delta_block_mask", real)
        runs.append((masked, mgr.stats.blocks_written, mgr.stats.bytes_written,
                     sorted(os.listdir(tmp_path / str(i)))))
    assert runs[0][0] and runs[0] == runs[1]


# ------------------------------------------------- one decision per object
class _Span:
    def __init__(self, stats):
        self.stats = stats

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **more):
        self.stats.update(more)


class _Spans:
    """Stands in for ``manager.span``: records each span's name and stats."""

    def __init__(self):
        self.opened = []

    def __call__(self, name, **stats):
        self.opened.append((name, stats))
        return _Span(stats)


_K1 = np.arange(256, dtype=np.float32)  # 16 blocks
_K2 = _K1.copy()
_K2[40] = -1.0  # one dirty block

#: case -> (mode, rewritten, value before (None: no image), value flushed,
#:          span opened, mask computed, arena method, dirty blocks in the span)
DECISIONS = {
    "delta-first": ("delta", (), None, _K1, "flush.mask", False, "rewrite", 16),
    "delta-later": ("delta", (), _K1, _K2, "flush.mask", True, "flush", 1),
    "delta-rewritten": ("delta", ("k",), _K1, _K2, "flush.whole", False, "rewrite", None),
    "full": ("full", (), _K1, _K2, "flush.whole", False, "rewrite", None),
    "delta-grown": ("delta", (), _K1, np.arange(272, dtype=np.float32), "flush.mask",
                    False, "rewrite", 17),
    "delta-unchanged": ("delta", (), _K1, _K1.copy(), "flush.mask", True, "flush", 0),
}


@pytest.mark.parametrize("case", list(DECISIONS))
def test_the_manager_decides_how_each_object_is_written(tmp_path, case, monkeypatch):
    from repro.core import delta_persist, manager

    mode, rewritten, before, live, opened, masks, method, dirty = DECISIONS[case]
    mgr = EasyCrashManager(NVMArena(backing_dir=str(tmp_path)),
                           FlushPolicy(leaves=("k",), persist_mode=mode), rewritten=rewritten)
    if before is not None:
        mgr._flush_now(1, {"k": before})
    spans, mask_calls, methods = _Spans(), [], []
    real_mask = delta_persist.delta_block_mask
    monkeypatch.setattr(manager, "span", spans)
    monkeypatch.setattr(manager, "tracing", lambda: True)
    monkeypatch.setattr(delta_persist, "delta_block_mask",
                        lambda c, l, b=64: mask_calls.append(l.shape) or real_mask(c, l, b))
    for m in ("flush", "rewrite"):
        real = getattr(NVMArena, m)
        monkeypatch.setattr(NVMArena, m, lambda self, *a, m=m, real=real:
                            methods.append(m) or real(self, *a))
    blocks, file_bytes = mgr.stats.blocks_written, mgr.stats.bytes_written

    mgr._flush_now(2, {"k": live})

    ((name, stats),) = spans.opened
    assert name == opened and stats["object"] == "k" and stats["nbytes"] == live.nbytes
    assert stats["blocks"] == -(-live.nbytes // 64)
    assert stats.get("dirty_blocks") == dirty
    assert mask_calls == ([live.shape] if masks else [])
    assert methods == [method]
    written = mgr.stats.blocks_written - blocks
    assert written == (stats["blocks"] if dirty is None else dirty)
    # an unchanged object reaches no file; any other is written out whole
    assert (mgr.stats.bytes_written > file_bytes) == (written > 0)
    assert mgr.arena.get("k").tobytes() == live.tobytes()


def test_the_auto_persist_mode_is_refused():
    with pytest.raises(ValueError, match="'delta' or 'full'"):
        FlushPolicy(leaves=("k",), persist_mode="auto")


@pytest.mark.parametrize("case", ["no image", "resized", "short mask"])
def test_a_masked_arena_flush_needs_an_image_of_its_size(tmp_path, case):
    arena = NVMArena(backing_dir=str(tmp_path))
    live = np.arange(256, dtype=np.float32)
    if case != "no image":
        arena.rewrite("k", live[:128] if case == "resized" else live)
    with pytest.raises(ValueError):
        arena.flush("k", live, np.zeros(16 if case != "short mask" else 15, bool))
    assert ("k" in arena) == (case != "no image")


# ------------------------------------------- masked flushes merge in place
def _kv(step, positions=12):
    """A bf16 K object (layers, rows, positions, heads, dims) holding
    ``step`` decoded positions; one position is 64 bytes, one block."""
    import ml_dtypes

    kv = np.zeros((2, 3, positions, 4, 8), ml_dtypes.bfloat16)
    kv[:, :, :step] = np.arange(1, step + 1)[None, None, :, None, None]
    return kv


def _in_order(a, layout):
    """``a``'s values in another memory order, as a device may hand them back."""
    if layout == "C":
        return a
    if layout == "F":
        return np.asfortranarray(a)
    return np.ascontiguousarray(a.transpose(2, 0, 1, 3, 4)).transpose(1, 2, 0, 3, 4)


def _file(tmp_path, name, like):
    return np.load(tmp_path / f"{name}.npy").view(like.dtype)


def _delta_flush(arena, step, layout="C"):
    from repro.core.delta_persist import delta_block_mask

    live = _in_order(_kv(step), layout)
    mask = delta_block_mask(arena.peek("k"), live, arena.block_bytes)
    assert 0 < mask.sum() < mask.size
    assert arena.flush("k", live, mask) == int(mask.sum())
    return live


@pytest.mark.parametrize("layout", ["C", "permuted"])
@pytest.mark.parametrize("reattached", [False, True], ids=["open", "reattached"])
def test_a_masked_flush_merges_into_the_arenas_own_image(tmp_path, reattached, layout):
    arena = NVMArena(backing_dir=str(tmp_path))
    arena.rewrite("k", _in_order(_kv(1), layout))  # first flush: written whole, row-major
    arena.save_manifest()
    if reattached:  # the image is now the one np.load gave
        arena = NVMArena.reattach(str(tmp_path))
    image = arena.peek("k")
    assert image.flags.c_contiguous
    for step in (2, 5):
        live = _delta_flush(arena, step, layout)
        assert arena.peek("k") is image  # merged in place, no fresh copy
        assert image.tobytes() == live.tobytes()
        assert _file(tmp_path, "k", live).tobytes() == live.tobytes()


def test_a_merged_image_shares_no_memory_with_the_flushed_value(tmp_path):
    arena = NVMArena(backing_dir=str(tmp_path))
    arena.rewrite("k", _kv(1))
    live = _delta_flush(arena, 3)
    want = live.tobytes()
    live[...] = 7  # the caller's array, reused after the flush
    arena.get("k")[...] = 9  # and the copy a load returns
    assert arena.get("k").tobytes() == want
    assert _file(tmp_path, "k", live).tobytes() == want


@pytest.mark.parametrize("entry", ["reattach", "install"])
def test_an_f_ordered_image_enters_the_arena_row_major(tmp_path, entry):
    """An image in Fortran order, from an older arena's file or handed to
    ``install``, is held row-major, so a masked flush merges into it in place."""
    first = np.asfortranarray(_kv(1))
    if entry == "reattach":
        np.save(tmp_path / "k.npy", first)
        (tmp_path / "manifest.json").write_text(
            json.dumps({"block_bytes": 64, "objects": {"k": "bfloat16"}}))
        arena = NVMArena.reattach(str(tmp_path))
    else:
        arena = NVMArena(backing_dir=str(tmp_path))
        arena.install("k", first)
    image = arena.peek("k")
    assert image.flags.c_contiguous and image.flags.writeable
    assert image.tobytes() == _kv(1).tobytes()
    live = _delta_flush(arena, 4)
    assert arena.peek("k") is image
    assert image.tobytes() == live.tobytes()
    assert _file(tmp_path, "k", live).tobytes() == live.tobytes()


@pytest.mark.parametrize("mode", ["full"])
def test_every_mode_leaves_the_files_of_delta_flushes(tmp_path, mode):
    """"full" rewrites every object whole: the files equal those of delta
    flushes, and the live values."""
    def run(m):
        d = tmp_path / m
        mgr = EasyCrashManager(NVMArena(backing_dir=str(d)),
                               FlushPolicy(leaves=("k",), every_steps=1, persist_mode=m))
        for step in (1, 2, 6):
            mgr.maybe_flush(step, {"k": _kv(step)})
        return {f: (d / f).read_bytes() for f in sorted(os.listdir(d))}

    assert run(mode) == run("delta")
    assert _file(tmp_path / mode, "k", _kv(6)).tobytes() == _kv(6).tobytes()


@pytest.mark.parametrize("layout", ["F", "permuted"])
def test_a_value_in_another_memory_order_is_staged_row_major(tmp_path, layout, monkeypatch):
    """The arena's blocks are row-major bytes: a live value in another order
    is staged row-major once, so its image is merged in place and its files
    are those of the same values in row-major order."""
    staged = []
    for method in ("flush", "rewrite"):
        real = getattr(NVMArena, method)
        monkeypatch.setattr(NVMArena, method, lambda self, name, live, *a, real=real:
                            staged.append(live.flags.c_contiguous) or real(self, name, live, *a))

    def run(d, order):
        mgr = EasyCrashManager(NVMArena(backing_dir=str(d)),
                               FlushPolicy(leaves=("k",), every_steps=1, persist_mode="delta"))
        images = []
        for step in (1, 2, 5):
            live = order(_kv(step))
            mgr.maybe_flush(step, {"k": live})
            images.append(mgr.arena.peek("k"))
        assert all(img is images[0] for img in images)
        assert images[0].flags.c_contiguous
        return {f: (d / f).read_bytes() for f in sorted(os.listdir(d))}

    other = run(tmp_path / layout, lambda a: _in_order(a, layout))
    assert not _in_order(_kv(1), layout).flags.c_contiguous
    assert other == run(tmp_path / "C", lambda a: a)
    assert len(staged) == 12 and all(staged)  # k and the step, 3 flushes, 2 runs


# ------------------------------- device values: masked where they live
def _device_state(step):
    """A decode cache on the device: bf16 K and V gaining one position (one
    block) a step, the position counter, a float32 object whose only block
    is partial, and a recurrent state that every step rewrites."""
    import jax.numpy as jnp

    return {"cache": {"k": jnp.asarray(_kv(step)), "v": jnp.asarray(-_kv(step)),
                      "t": jnp.asarray(step, jnp.int32),
                      "odd": jnp.arange(7, dtype=jnp.float32) * step,
                      "ssm": jnp.full((3, 5), step, jnp.float32)}}


DEVICE_MASKED = ["cache/k", "cache/odd", "cache/t", "cache/v"]


def _device_manager(arena):
    return EasyCrashManager(arena, FlushPolicy(leaves=("cache",)), rewritten=("cache/ssm",))


def _assert_images_are_the_arenas(mgr):
    assert sorted(mgr._images) == DEVICE_MASKED
    for name, (image, _) in mgr._images.items():
        assert np.asarray(image).tobytes() == mgr.arena.peek(name).tobytes(), name


@pytest.mark.parametrize("mask", ["kernel", "nothing dirty"])
def test_the_device_image_is_the_arena_image_after_every_flush(tmp_path, mask, monkeypatch):
    import jax

    from repro.core import delta_persist

    masks = []
    real = delta_persist.delta_block_mask

    def chosen(cur, live, block_bytes=64):
        got = real(cur, live, block_bytes)
        masks.append(isinstance(live, jax.Array))
        return got if mask == "kernel" else np.zeros_like(got)

    monkeypatch.setattr(delta_persist, "delta_block_mask", chosen)
    mgr = _device_manager(NVMArena(backing_dir=str(tmp_path)))
    for step in (1, 2, 5):
        mgr.maybe_flush(step, _device_state(step))
        _assert_images_are_the_arenas(mgr)
    # the later flushes masked the four objects on the device, the step on the host
    assert masks == [True] * 4 + [False] + [True] * 4 + [False]
    kept = 5 if mask == "kernel" else 1  # a mask that marks nothing leaves the first flush
    for name, value in flatten_state(_device_state(kept)).items():
        if name != "cache/ssm":
            assert mgr.arena.peek(name).tobytes() == np.asarray(value).tobytes(), name
    assert mgr.arena.peek("cache/ssm").tobytes() == np.asarray(_device_state(5)["cache"]["ssm"]).tobytes()


@pytest.mark.parametrize("how", ["reattach", "install"])
def test_the_device_image_is_rebuilt_from_an_arena_image_it_did_not_write(
        tmp_path, how, monkeypatch):
    """After a reattach (the resume path), or an image installed in the
    arena, the first masked flush masks against the arena's image."""
    import jax

    from repro.core import delta_persist

    mgr = _device_manager(NVMArena(backing_dir=str(tmp_path / "crashed")))
    whole = _device_manager(NVMArena(backing_dir=str(tmp_path / "whole")))
    for step in (1, 2, 5):
        whole.maybe_flush(step, _device_state(step))
    for step in (1, 2):
        mgr.maybe_flush(step, _device_state(step))
    at = flatten_state(_device_state(2))
    if how == "reattach":
        mgr = _device_manager(NVMArena.reattach(str(tmp_path / "crashed")))
        assert not mgr._images
    else:
        at["cache/k"] = _kv(4)
        mgr.arena.install("cache/k", at["cache/k"])
    compared = []
    real = delta_persist.delta_block_mask

    def record(cur, live, block_bytes=64):
        if isinstance(cur, jax.Array):
            compared.append(np.asarray(cur).tobytes())
        return real(cur, live, block_bytes)

    monkeypatch.setattr(delta_persist, "delta_block_mask", record)
    mgr.maybe_flush(5, _device_state(5))
    # each device object was masked against its image as the arena held it
    assert compared == [np.asarray(at[n]).tobytes()
                        for n in ("cache/k", "cache/v", "cache/t", "cache/odd")]
    _assert_images_are_the_arenas(mgr)
    for f in sorted(os.listdir(tmp_path / "whole")):
        assert (tmp_path / "crashed" / f).read_bytes() == (tmp_path / "whole" / f).read_bytes(), f


def test_no_buffer_the_manager_keeps_is_deleted_by_a_donating_step(tmp_path):
    """The state handed to a flush is the state the next step donates: the
    manager keeps copies of its own, and no read of the flush outlives it."""
    import jax

    advance = jax.jit(lambda s: jax.tree.map(lambda a: a + 1, s), donate_argnums=0)
    mgr = _device_manager(NVMArena(backing_dir=str(tmp_path)))
    state = _device_state(1)
    for step in (1, 2, 3):
        mgr.maybe_flush(step, state)
        handed = jax.tree.leaves(state)
        state = advance(state)
        assert all(a.is_deleted() for a in handed)  # the step did donate them
        assert not any(image.is_deleted() for image, _ in mgr._images.values())
        _assert_images_are_the_arenas(mgr)
    mgr.maybe_flush(4, state)
    for name, value in flatten_state(state).items():
        assert mgr.arena.peek(name).tobytes() == np.asarray(value).tobytes(), name


SESSION = ["--width", "64", "--prompts", "2", "--prompt-len", "8", "--decode-steps", "12",
           "--flush-every", "4", "--seed", "2147483651"]


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "granite-4.0-h-micro"])
def test_a_delta_session_writes_the_files_of_a_full_one_and_of_host_values(
        tmp_path, arch, monkeypatch):
    """The server's cache, masked on the device, leaves the arena files of a
    session that writes it whole, and of one that hands the manager host
    copies of the same cache (the host mask)."""
    import jax

    from repro.core import delta_persist
    from repro.launch import serve

    on_device = []
    real = delta_persist.delta_block_mask
    monkeypatch.setattr(delta_persist, "delta_block_mask", lambda c, l, b=64: on_device.append(
        isinstance(l, jax.Array)) or real(c, l, b))

    def files(run, mode="delta"):
        serve.main(["--arch", arch, *SESSION, "--persist-mode", mode,
                    "--workdir", str(tmp_path / run)])
        d = tmp_path / run / "serve_arena"
        return {f: (d / f).read_bytes() for f in sorted(os.listdir(d))}

    delta = files("delta")
    assert any(on_device)
    assert delta == files("full")
    on_device.clear()
    monkeypatch.setattr(serve, "_copy", lambda cache: jax.tree.map(np.asarray, cache))
    assert delta == files("host")
    assert on_device and not any(on_device)
