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
    policy = FlushPolicy(leaves=("params",), every_steps=1, async_flush=False)
    mgr = EasyCrashManager(arena, policy)
    mgr.maybe_flush(5, _state(5))
    mgr.close()

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
    policy = FlushPolicy(leaves=("params",), every_steps=1, async_flush=False)
    mgr = EasyCrashManager(arena, policy)
    s = _state(1)
    mgr.maybe_flush(1, s)
    first = arena.stats.flush_writes
    mgr.maybe_flush(2, s)  # identical values: delta flush writes ~nothing
    second = arena.stats.flush_writes - first
    # only the __step__ scalar changed
    assert second <= 1
    assert arena.stats.flushed_clean_blocks > 0
    mgr.close()


def test_flush_cadence():
    arena = NVMArena()
    policy = FlushPolicy(leaves=("params",), every_steps=4, async_flush=False)
    mgr = EasyCrashManager(arena, policy)
    issued = [mgr.maybe_flush(s, _state(s)) for s in range(8)]
    assert issued == [True, False, False, False, True, False, False, False]


@pytest.mark.parametrize("every", [1, 3, 8])
def test_due_is_where_maybe_flush_flushes(every):
    mgr = EasyCrashManager(NVMArena(), FlushPolicy(leaves=("params",), every_steps=every,
                                                   async_flush=False))
    due = [mgr.due(s) for s in range(25)]
    issued = [mgr.maybe_flush(s, _state(s)) for s in range(25)]
    assert due == issued
    assert mgr.stats.flushes_issued == sum(due) == len(range(0, 25, every))


def test_async_flush_barrier(tmp_path):
    arena = NVMArena(backing_dir=str(tmp_path))
    policy = FlushPolicy(leaves=("params", "opt"), every_steps=1,
                         async_flush=True, max_pending=16)
    mgr = EasyCrashManager(arena, policy)
    for s in range(4):
        mgr.maybe_flush(s, _state(s))
    mgr.barrier()
    assert "params/w" in arena
    assert int(arena.get("__step__")) == 3
    mgr.close()


def test_async_backpressure_skips():
    """Straggler mitigation: an overloaded flush queue skips, never blocks."""
    import threading, queue as q

    arena = NVMArena()
    policy = FlushPolicy(leaves=("params",), every_steps=1,
                         async_flush=True, max_pending=1)
    mgr = EasyCrashManager(arena, policy)
    # stall the worker by grabbing the queue first
    for s in range(50):
        mgr.maybe_flush(s, _state(s))
    assert mgr.stats.flushes_skipped + mgr.stats.flushes_issued == 50
    mgr.close()


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
    policy = FlushPolicy(leaves=("params",), every_steps=1, async_flush=False)
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
    policy = FlushPolicy(leaves=("params",), async_flush=False)
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
    flush, the masks asked for: (an image existed, object shape, dirty
    blocks or None)."""
    from repro.core import delta_persist

    masked = []
    real = delta_persist.persist_mask_for

    def record(mode_, cur, live, block_bytes=64):
        mask = real(mode_, cur, live, block_bytes)
        masked.append((cur is not None, live.shape,
                       None if mask is None else int(np.count_nonzero(mask))))
        return mask

    monkeypatch.setattr(delta_persist, "persist_mask_for", record)
    arena = NVMArena(backing_dir=str(tmp_path))
    policy = FlushPolicy(leaves=("cache", "tokens"), every_steps=1, async_flush=False,
                         persist_mode=mode)
    mgr = EasyCrashManager(arena, policy, rewritten=rewritten)
    calls = []
    for step in steps:
        before = len(masked)
        mgr.maybe_flush(step, _hybrid_state(step))
        calls.append(masked[before:])
    mgr.close()
    return mgr, calls


@pytest.mark.parametrize("mode", ["auto", "delta", "full"])
def test_whole_written_objects_open_no_mask(tmp_path, mode, monkeypatch):
    from repro.core.blocks import obj_num_blocks

    mgr, calls = _flush_steps(tmp_path, mode, REWRITTEN, monkeypatch)
    state = flatten_state(_hybrid_state(3))
    whole = {n for n in state if n.startswith(REWRITTEN[0] + "/")}
    assert whole == {"cache/group0/pos0/ssm", "cache/group0/pos0/conv"}
    # every other object of the flush, and the step, asks for its mask
    for step, call in zip((1, 2, 3), calls):
        flat = flatten_state(_hybrid_state(step))
        shapes = [str(flat[n].shape) for n in flat if n not in whole] + ["()"]
        assert sorted(str(shape) for _, shape, _ in call) == sorted(shapes)
    # each flush writes every block of the rewritten objects
    per_flush = sum(obj_num_blocks(state[n], 64) for n in whole)
    assert mgr.stats.blocks_written >= 3 * per_flush


def test_kv_keeps_its_delta_mask_beside_whole_written_state(tmp_path, monkeypatch):
    _, calls = _flush_steps(tmp_path, "delta", REWRITTEN, monkeypatch)
    kv_shape = (2, 3, 16, 4)
    # K and V: written whole at the first flush (no image yet), then masked:
    # a step adds one 16-byte position, one dirty 64-byte block in each of
    # the 2 x 3 (layer, row) slices
    assert [[(seen, dirty) for seen, shape, dirty in call if shape == kv_shape]
            for call in calls] == [[(False, None)] * 2, [(True, 6)] * 2, [(True, 6)] * 2]


@pytest.mark.parametrize("mode", ["auto", "delta", "full"])
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
        real = delta_persist.persist_mask_for
        monkeypatch.setattr(delta_persist, "persist_mask_for",
                            lambda m, c, l, b=64, real=real: masked.append(l.shape)
                            or real(m, c, l, b))
        arena = NVMArena(backing_dir=str(tmp_path / str(i)))
        mgr = EasyCrashManager(arena, FlushPolicy(leaves=("cache", "tokens"), every_steps=1,
                                                  async_flush=False, persist_mode="delta"),
                               rewritten=rewritten)
        for step in (1, 2, 3):
            mgr.maybe_flush(step, attention_state(step))
        mgr.close()
        monkeypatch.setattr(delta_persist, "persist_mask_for", real)
        runs.append((masked, mgr.stats.blocks_written, mgr.stats.bytes_written,
                     sorted(os.listdir(tmp_path / str(i)))))
    assert runs[0] == runs[1]


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
    from repro.core.delta_persist import persist_mask_for

    live = _in_order(_kv(step), layout)
    mask = persist_mask_for("delta", arena.peek("k"), live, arena.block_bytes)
    assert mask is not None and 0 < mask.sum() < mask.size
    assert arena.flush("k", live, dirty_resident_mask=mask) == int(mask.sum())
    return live


@pytest.mark.parametrize("layout", ["C", "permuted"])
@pytest.mark.parametrize("reattached", [False, True], ids=["open", "reattached"])
def test_a_masked_flush_merges_into_the_arenas_own_image(tmp_path, reattached, layout):
    arena = NVMArena(backing_dir=str(tmp_path))
    arena.flush("k", _in_order(_kv(1), layout))  # first flush: written whole, row-major
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
    arena.flush("k", _kv(1))
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


@pytest.mark.parametrize("mode", ["full", "auto"])
def test_every_mode_leaves_the_files_of_delta_flushes(tmp_path, mode):
    """"full" marks every block, which merges as one copy of the whole
    image, and "auto" merges the arena's own diff: the files equal those of
    delta flushes, and the live values."""
    def run(m):
        d = tmp_path / m
        mgr = EasyCrashManager(NVMArena(backing_dir=str(d)),
                               FlushPolicy(leaves=("k",), every_steps=1, async_flush=False,
                                           persist_mode=m))
        for step in (1, 2, 6):
            mgr.maybe_flush(step, {"k": _kv(step)})
        mgr.close()
        return {f: (d / f).read_bytes() for f in sorted(os.listdir(d))}

    assert run(mode) == run("delta")
    assert _file(tmp_path / mode, "k", _kv(6)).tobytes() == _kv(6).tobytes()


@pytest.mark.parametrize("layout", ["F", "permuted"])
def test_a_value_in_another_memory_order_is_staged_row_major(tmp_path, layout, monkeypatch):
    """The arena's blocks are row-major bytes: a live value in another order
    is staged row-major once, so its image is merged in place and its files
    are those of the same values in row-major order."""
    staged = []
    real_flush = NVMArena.flush
    monkeypatch.setattr(NVMArena, "flush", lambda self, name, live, *a, **k: staged.append(
        live.flags.c_contiguous) or real_flush(self, name, live, *a, **k))

    def run(d, order):
        mgr = EasyCrashManager(NVMArena(backing_dir=str(d)),
                               FlushPolicy(leaves=("k",), every_steps=1, async_flush=False,
                                           persist_mode="delta"))
        images = []
        for step in (1, 2, 5):
            live = order(_kv(step))
            mgr.maybe_flush(step, {"k": live})
            images.append(mgr.arena.peek("k"))
        mgr.close()
        assert all(img is images[0] for img in images)
        assert images[0].flags.c_contiguous
        return {f: (d / f).read_bytes() for f in sorted(os.listdir(d))}

    other = run(tmp_path / layout, lambda a: _in_order(a, layout))
    assert not _in_order(_kv(1), layout).flags.c_contiguous
    assert other == run(tmp_path / "C", lambda a: a)
    assert len(staged) == 12 and all(staged)  # k and the step, 3 flushes, 2 runs
