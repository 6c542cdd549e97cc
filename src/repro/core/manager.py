"""EasyCrash production runtime for distributed training loops.

This is the framework-facing layer: given a train-state pytree and a
:class:`PersistPlan`-style policy, the manager

* flushes the plan's state leaves to a host-local :class:`NVMArena`
  (asynchronously, on a writer thread — a straggling host never blocks the
  step, and a skipped flush only increases staleness, which EasyCrash
  tolerates by construction);
* performs delta flushes: only blocks that changed since the last flush
  move, as flagged by the ``delta_snapshot`` Pallas kernel; objects that
  every step rewrites whole (a model's recurrent state, named by the
  state's layout, not by a user) are written whole with no mask;
* takes full coordinated checkpoints at the Young interval stretched by the
  measured recomputability (MTBF' = MTBF / (1 - R));
* on restart, tries the EasyCrash path (arena image + acceptance
  verification) before falling back to the last full checkpoint.

Every host persists only its own shards: the mechanism is O(local bytes) and
has zero cross-host traffic, so it scales to arbitrarily many nodes.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import numpy as np

from ..telemetry import span, tracing
from .arena import NVMArena
from .blocks import obj_num_blocks
from .efficiency import young_interval


def _cast_like(img: np.ndarray, target: np.ndarray) -> np.ndarray:
    """Cast a loaded array to the target dtype; np.load round-trips extension
    dtypes (bfloat16) as raw void bytes, which only ``view`` can recover."""
    if img.dtype == target.dtype:
        return img
    if img.dtype.kind == "V" and img.dtype.itemsize == target.dtype.itemsize:
        return img.view(target.dtype)
    return img.astype(target.dtype)


def flatten_state(state: Mapping[str, Any], prefix: str = "") -> Dict[str, np.ndarray]:
    """Flatten a nested dict pytree of arrays into 'a/b/c' -> ndarray."""
    out: Dict[str, np.ndarray] = {}
    for k, v in state.items():
        key = f"{prefix}{k}"
        if isinstance(v, Mapping):
            out.update(flatten_state(v, key + "/"))
        else:
            out[key] = np.asarray(v)
    return out


def unflatten_state(flat: Mapping[str, np.ndarray]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in flat.items():
        parts = k.split("/")
        d = out
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return out


@dataclass
class FlushPolicy:
    """Production analogue of :class:`PersistPlan`.

    ``leaves``: state leaves (flat names, prefix match allowed) to persist.
    ``every_steps``: flush cadence in optimizer steps (the 'frequency x').
    ``async_flush``: persist on a background thread (drops to sync in tests).
    ``max_pending``: back-pressure bound; beyond it flushes are *skipped*
    (bounded staleness instead of a stalled step — straggler mitigation).
    ``persist_mode``: which blocks a flush moves to NVM —
    ``"auto"`` (arena's own byte diff), ``"delta"`` (incremental: changed
    blocks only, detected by the ``delta_snapshot`` kernel) or ``"full"``
    (whole-object rewrite, the C/R-style baseline).  All three produce
    byte-identical NVM images; they differ in the blocks they mark dirty,
    which ``ManagerStats.blocks_written`` counts.  ``bytes_written`` counts
    what the arena's backing files received: an object with any dirty block
    is rewritten whole.
    """

    leaves: Tuple[str, ...]
    every_steps: int = 1
    async_flush: bool = True
    max_pending: int = 2
    persist_mode: str = "auto"

    def __post_init__(self):
        if self.persist_mode not in ("auto", "delta", "full"):
            raise ValueError(
                f"unknown persist_mode {self.persist_mode!r}; use 'auto', 'delta' or 'full'"
            )


@dataclass
class ManagerStats:
    flushes_issued: int = 0
    flushes_skipped: int = 0
    #: dirty blocks the flushes wrote into the arena's images
    blocks_written: int = 0
    #: bytes the flushes wrote to the arena's backing files (0 without files)
    bytes_written: int = 0
    checkpoints_taken: int = 0
    easycrash_restores: int = 0
    checkpoint_restores: int = 0


class EasyCrashManager:
    def __init__(
        self,
        arena: NVMArena,
        policy: FlushPolicy,
        checkpoint_save: Optional[Callable[[int, Mapping[str, Any]], None]] = None,
        checkpoint_restore: Optional[Callable[[], Optional[Tuple[int, Dict[str, Any]]]]] = None,
        mtbf: Optional[float] = None,
        t_chk: Optional[float] = None,
        recomputability: float = 0.0,
        step_time: float = 1.0,
        rewritten: Sequence[str] = (),
    ):
        """``rewritten``: state leaves (flat names, matched as
        ``FlushPolicy.leaves`` are) that every step rewrites whole, as the
        state's layout says; a flush writes them whole with no mask, whatever
        ``persist_mode`` says of the rest."""
        self.arena = arena
        self.policy = policy
        self.rewritten = tuple(rewritten)
        self.checkpoint_save = checkpoint_save
        self.checkpoint_restore = checkpoint_restore
        self.stats = ManagerStats()
        self._q: "queue.Queue[Optional[Tuple[int, Dict[str, np.ndarray]]]]" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._last_error: Optional[BaseException] = None
        if policy.async_flush:
            self._worker = threading.Thread(target=self._drain, daemon=True)
            self._worker.start()
        # checkpoint cadence in *steps*, from Young's formula on the stretched
        # MTBF (paper §7); None disables periodic checkpoints.
        self.checkpoint_every: Optional[int] = None
        if mtbf is not None and t_chk is not None:
            mtbf_ec = mtbf / max(1e-9, (1.0 - min(recomputability, 0.999999)))
            self.checkpoint_every = max(1, int(young_interval(t_chk, mtbf_ec) / step_time))

    # ------------------------------------------------------------------ flush
    @staticmethod
    def _match(name: str, leaf: str) -> bool:
        if leaf.endswith("*"):
            return name.startswith(leaf[:-1])
        return name == leaf or name.startswith(leaf + "/")

    def _selected(self, flat: Mapping[str, np.ndarray]) -> Dict[str, np.ndarray]:
        return {
            name: arr
            for name, arr in flat.items()
            if any(self._match(name, l) for l in self.policy.leaves)
        }

    def due(self, step: int) -> bool:
        """Whether the cadence flushes at ``step``: a caller can leave the
        state on the device on the steps where it does not."""
        return step % self.policy.every_steps == 0

    def maybe_flush(self, step: int, state: Mapping[str, Any]) -> bool:
        """Issue an EasyCrash persistence op if the cadence says so.

        Returns True if a flush was issued (or enqueued)."""
        if not self.due(step):
            return False
        with span("flush", step=step, mode=self.policy.persist_mode):
            with span("flush.stage") as stage:
                flat = flatten_state(state)
                sel = self._selected(flat)
                sel["__step__"] = np.asarray(step, dtype=np.int64)
                # row-major, as the arena's blocks are: an array fetched from a
                # device may come in another order, which every later byte view
                # (mask, merge, file) would otherwise copy again to reorder
                payload = {k: np.array(v, copy=True, order="C") for k, v in sel.items()}
                if tracing():
                    stage.add(nbytes=sum(v.nbytes for v in payload.values()))
            if self.policy.async_flush:
                if self._q.qsize() >= self.policy.max_pending:
                    self.stats.flushes_skipped += 1   # straggler mitigation: skip
                    return False
                self._q.put((step, payload))
            else:
                self._flush_now(step, payload)
        self.stats.flushes_issued += 1
        return True

    def _flush_now(self, step: int, payload: Mapping[str, np.ndarray]) -> None:
        from .delta_persist import persist_mask_for

        block = self.arena.block_bytes
        file_bytes = self.arena.file_bytes
        for name, arr in payload.items():
            if any(self._match(name, leaf) for leaf in self.rewritten):
                with span("flush.whole", object=name, nbytes=arr.nbytes,
                          blocks=obj_num_blocks(arr, block)):
                    self.stats.blocks_written += self.arena.rewrite(name, arr)
                continue
            cur = self.arena.peek(name)
            with span("flush.mask", object=name, nbytes=arr.nbytes,
                      block_bytes=block) as s:
                mask = persist_mask_for(self.policy.persist_mode, cur, arr, block)
                if tracing():
                    blocks = obj_num_blocks(arr, block)
                    if mask is not None:
                        s.add(blocks=blocks, dirty_blocks=int(np.count_nonzero(mask)))
                    elif cur is None or cur.nbytes != arr.nbytes:
                        s.add(blocks=blocks, dirty_blocks=blocks)  # first flush: all
                    else:
                        s.add(blocks=blocks)  # "auto": the arena diffs
            written = self.arena.flush(name, arr, dirty_resident_mask=mask)
            self.stats.blocks_written += written
        self.arena.save_manifest()
        self.stats.bytes_written += self.arena.file_bytes - file_bytes

    def _drain(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            try:
                self._flush_now(*item)
            except BaseException as e:  # surfaced on barrier()
                self._last_error = e

    def barrier(self) -> None:
        """Wait for all pending flushes (checkpoint/shutdown boundary)."""
        if self.policy.async_flush:
            while not self._q.empty():
                time.sleep(0.001)
            # one more roundtrip so an in-flight item finishes
            self._q.put((int(-1), {}))
            while not self._q.empty():
                time.sleep(0.001)
        if self._last_error is not None:
            raise self._last_error

    def close(self) -> None:
        if self._worker is not None:
            self.barrier()
            self._q.put(None)
            self._worker.join(timeout=5)
            self._worker = None

    # ------------------------------------------------------------- checkpoint
    def maybe_checkpoint(self, step: int, state: Mapping[str, Any]) -> bool:
        if (
            self.checkpoint_save is None
            or self.checkpoint_every is None
            or step == 0
            or step % self.checkpoint_every != 0
        ):
            return False
        self.barrier()
        self.checkpoint_save(step, state)
        self.stats.checkpoints_taken += 1
        return True

    # ---------------------------------------------------------------- restore
    def restore(
        self,
        init_state: Mapping[str, Any],
        verify: Optional[Callable[[Dict[str, Any], int], bool]] = None,
    ) -> Tuple[Dict[str, Any], int, str]:
        """Recovery: EasyCrash path first, checkpoint fallback second.

        ``verify(state, step)`` is the acceptance hook deciding whether the
        NVM image is usable; recomputability-by-construction means it may
        accept inconsistent-but-convergent images.
        Returns (state, step, source) with source in
        {"easycrash", "checkpoint", "fresh"}.
        """
        flat_init = flatten_state(init_state)
        # --- EasyCrash path: arena image over init state
        names = set(self.arena.names())
        if "__step__" in names:
            merged = dict(flat_init)
            for name in names:
                if name == "__step__" or name.startswith("__chk__/"):
                    continue
                if name in merged:
                    img = self.arena.get(name)
                    if img.shape == merged[name].shape:
                        merged[name] = _cast_like(img, merged[name])
            step = int(self.arena.get("__step__"))
            candidate = unflatten_state(merged)
            if verify is None or verify(candidate, step):
                self.stats.easycrash_restores += 1
                return candidate, step, "easycrash"
        # --- checkpoint fallback
        if self.checkpoint_restore is not None:
            got = self.checkpoint_restore()
            if got is not None:
                step, state = got
                self.stats.checkpoint_restores += 1
                return state, step, "checkpoint"
        return dict(init_state), 0, "fresh"
