"""EasyCrash production runtime for distributed training loops.

This is the framework-facing layer: given a train-state pytree and a
:class:`PersistPlan`-style policy, the manager

* flushes the plan's state leaves to a host-local :class:`NVMArena` on the
  policy's cadence, synchronously, in the caller's thread;
* decides, per object, how a flush writes it: masked (only blocks that
  changed since the last flush move, as flagged by the ``delta_snapshot``
  Pallas kernel) or whole (every block: in ``"full"`` mode, and for objects
  that every step rewrites whole, such as a model's recurrent state, named
  by the state's layout, not by a user);
* takes full coordinated checkpoints at the Young interval stretched by the
  measured recomputability (MTBF' = MTBF / (1 - R));
* on restart, tries the EasyCrash path (arena image + acceptance
  verification) before falling back to the last full checkpoint.

Every host persists only its own shards: the mechanism is O(local bytes) and
has zero cross-host traffic, so it scales to arbitrarily many nodes.
"""
from __future__ import annotations

from dataclasses import dataclass
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

    ``leaves``: state leaves to persist (flat names; a name also selects
    the leaves under it, ``"cache"`` selects ``"cache/t"``).
    ``every_steps``: flush cadence in optimizer steps (the 'frequency x').
    ``persist_mode``: which blocks a flush moves to NVM —
    ``"delta"`` (incremental: changed blocks only, detected by the
    ``delta_snapshot`` kernel) or ``"full"`` (whole-object rewrite, the
    C/R-style baseline).  Both produce byte-identical NVM images; they differ
    in the blocks they write, which ``ManagerStats.blocks_written`` counts.
    ``bytes_written`` counts what the arena's backing files received: an
    object with any dirty block is rewritten whole.
    """

    leaves: Tuple[str, ...]
    every_steps: int = 1
    persist_mode: str = "delta"

    def __post_init__(self):
        if self.persist_mode not in ("delta", "full"):
            raise ValueError(
                f"unknown persist_mode {self.persist_mode!r}; use 'delta' or 'full'"
            )


@dataclass
class ManagerStats:
    flushes_issued: int = 0
    #: blocks the flushes wrote into the arena's images
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
        # checkpoint cadence in *steps*, from Young's formula on the stretched
        # MTBF (paper §7); None disables periodic checkpoints.
        self.checkpoint_every: Optional[int] = None
        if mtbf is not None and t_chk is not None:
            mtbf_ec = mtbf / max(1e-9, (1.0 - min(recomputability, 0.999999)))
            self.checkpoint_every = max(1, int(young_interval(t_chk, mtbf_ec) / step_time))

    # ------------------------------------------------------------------ flush
    @staticmethod
    def _match(name: str, leaf: str) -> bool:
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
        """Flush the policy's leaves of ``state`` if the cadence says so.

        Returns True if a flush ran."""
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
            self._flush_now(step, payload)
        self.stats.flushes_issued += 1
        return True

    def _flush_now(self, step: int, payload: Mapping[str, np.ndarray]) -> None:
        """Write each object of ``payload`` into the arena: the one place that
        decides whether an object is written whole or masked."""
        from . import delta_persist  # looked up per call, so it can be wrapped

        block = self.arena.block_bytes
        file_bytes = self.arena.file_bytes
        full = self.policy.persist_mode == "full"
        for name, arr in payload.items():
            blocks = obj_num_blocks(arr, block)
            if full or any(self._match(name, leaf) for leaf in self.rewritten):
                with span("flush.whole", object=name, nbytes=arr.nbytes, blocks=blocks):
                    self.stats.blocks_written += self.arena.rewrite(name, arr)
                continue
            cur = self.arena.peek(name)
            mask = None
            with span("flush.mask", object=name, nbytes=arr.nbytes,
                      block_bytes=block) as s:
                if cur is not None and cur.nbytes == arr.nbytes:
                    mask = delta_persist.delta_block_mask(cur, arr, block)
                if tracing():
                    dirty = blocks if mask is None else int(np.count_nonzero(mask))
                    s.add(blocks=blocks, dirty_blocks=dirty)
            if mask is None:  # a first flush, or an object that changed size
                self.stats.blocks_written += self.arena.rewrite(name, arr)
            else:
                self.stats.blocks_written += self.arena.flush(name, arr, mask)
        self.arena.save_manifest()
        self.stats.bytes_written += self.arena.file_bytes - file_bytes

    # ------------------------------------------------------------- checkpoint
    def maybe_checkpoint(self, step: int, state: Mapping[str, Any]) -> bool:
        if (
            self.checkpoint_save is None
            or self.checkpoint_every is None
            or step == 0
            or step % self.checkpoint_every != 0
        ):
            return False
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
