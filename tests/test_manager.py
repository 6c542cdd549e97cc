"""Production runtime: arena durability, flush/restore, checkpoint fallback."""
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
