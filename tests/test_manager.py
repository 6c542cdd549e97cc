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
