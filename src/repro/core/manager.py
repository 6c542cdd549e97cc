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
* masks a device value on the device, against a device copy of its last
  flushed image, and brings only the dirty blocks to the host; a value
  written whole crosses once, flattened on the device so that it arrives
  row-major;
* takes full coordinated checkpoints at the Young interval stretched by the
  measured recomputability (MTBF' = MTBF / (1 - R));
* on restart, tries the EasyCrash path (arena image + acceptance
  verification) before falling back to the last full checkpoint.

Every host persists only its own shards: the mechanism is O(local bytes) and
has zero cross-host traffic, so it scales to arbitrarily many nodes.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import span, tracing
from .arena import NVMArena
from .blocks import num_blocks
from .efficiency import young_interval


def _cast_like(img: np.ndarray, target: np.ndarray) -> np.ndarray:
    """Cast a loaded array to the target dtype; np.load round-trips extension
    dtypes (bfloat16) as raw void bytes, which only ``view`` can recover."""
    if img.dtype == target.dtype:
        return img
    if img.dtype.kind == "V" and img.dtype.itemsize == target.dtype.itemsize:
        return img.view(target.dtype)
    return img.astype(target.dtype)


def flatten_state(state: Mapping[str, Any], prefix: str = "") -> Dict[str, Any]:
    """Flatten a nested dict pytree of arrays into 'a/b/c' -> array: device
    arrays stay where they are, anything else becomes an ndarray."""
    out: Dict[str, Any] = {}
    for k, v in state.items():
        key = f"{prefix}{k}"
        if isinstance(v, Mapping):
            out.update(flatten_state(v, key + "/"))
        else:
            out[key] = v if isinstance(v, jax.Array) else np.asarray(v)
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


#: lanes of a TPU tile: a row of the dirty-block gather is the fewest whole
#: blocks across them, so rows move whole tiles
LANES = 128


@jax.jit
def _flat_copy(x):
    """``x`` flat and row-major, in its dtype, in a buffer of its own: never
    the caller's, which a later donating call may delete. Fetched, it
    arrives row-major whatever the device's layout of ``x``."""
    return jnp.array(x.reshape(-1), copy=True)


@jax.jit
def _flat_bytes(x):
    """``x``'s bytes, as :func:`_flat_copy` gives its elements: the device
    copy of an object masked on the device, so that its mask runs the
    program the host mask compiles for an object of that size."""
    # through rows of lanes: the TPU's compiler lays that out far faster than one long axis
    flat = x.reshape(-1, LANES) if x.size % LANES == 0 else x.reshape(-1)
    if x.dtype == jnp.bool_:
        return flat.astype(jnp.uint8).reshape(-1)
    return jnp.array(jax.lax.bitcast_convert_type(flat, jnp.uint8).reshape(-1), copy=True)


@functools.partial(jax.jit, static_argnames=("block_elems",))
def _merge_rows(image, live, idx, marked, *, block_elems: int):
    """Rows ``idx`` of the flat ``image``, each block that ``marked`` (a flag
    per block of each row) sets taken from the flat ``live``: the merged rows
    for the host, and ``image`` with them set, which then holds the marked
    blocks and no other change."""
    row = marked.shape[1] * block_elems
    n_rows = -(-live.size // row)
    pad = n_rows * row - live.size

    def rows(a):
        return jnp.pad(a, (0, pad)).reshape(n_rows, row)

    old = rows(image)
    merged = jnp.where(jnp.repeat(marked, block_elems, axis=1), rows(live)[idx], old[idx])
    # the rows stay rows: flattened, the TPU's compiler lays them out anew
    return merged, old.at[idx].set(merged).reshape(-1)[:live.size]


def _on_device(arr) -> bool:
    """Whether ``arr`` is a device array whose bytes the device can view: of
    a numeric or bool dtype."""
    return isinstance(arr, jax.Array) and (
        arr.dtype == jnp.bool_ or jnp.issubdtype(arr.dtype, jnp.integer)
        or jnp.issubdtype(arr.dtype, jnp.floating))


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
        #: per object masked on the device: a flat device copy of its arena
        #: image's bytes, and the image it copies (a cache, rebuilt from the arena
        #: where the image is no longer that array, as after a reattach)
        self._images: Dict[str, Tuple[jax.Array, np.ndarray]] = {}
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
                sel = self._selected(flatten_state(state))
                sel["__step__"] = np.asarray(step, dtype=np.int64)
                # row-major, as the arena's blocks are: a host array in another
                # order is copied once here, not again in every byte view (mask,
                # merge, file); a device value is flattened on the device
                payload = {k: v if isinstance(v, jax.Array) or v.flags.c_contiguous
                           else np.array(v, order="C") for k, v in sel.items()}
                if tracing():
                    stage.add(nbytes=sum(v.nbytes for k, v in payload.items()
                                         if v is not sel[k]))
            self._flush_now(step, payload)
        self.stats.flushes_issued += 1
        return True

    def _flush_now(self, step: int, payload: Mapping[str, Any]) -> None:
        """Write each object of ``payload`` into the arena: the one place that
        decides whether an object is written whole or masked, and where."""
        from . import delta_persist  # looked up per call, so it can be wrapped

        block = self.arena.block_bytes
        file_bytes = self.arena.file_bytes
        full = self.policy.persist_mode == "full"
        for name, arr in payload.items():
            blocks = num_blocks(arr.nbytes, block)
            prior = self._images.pop(name, None)  # set again once the arena has the flush
            if full or any(self._match(name, leaf) for leaf in self.rewritten):
                with span("flush.whole", object=name, nbytes=arr.nbytes, blocks=blocks):
                    self.stats.blocks_written += self.arena.rewrite(
                        name, self._fetch(name, arr, as_bytes=False)[0])
                continue
            cur = self.arena.peek(name)
            resized = cur is None or cur.nbytes != arr.nbytes
            on_device = not resized and _on_device(arr)
            with span("flush.mask", object=name, nbytes=arr.nbytes,
                      block_bytes=block) as s:
                if on_device:
                    image = prior[0] if prior is not None and prior[1] is cur else \
                        jax.device_put(cur.reshape(-1).view(np.uint8), may_alias=False)
                    live = _flat_bytes(arr)
                    mask = delta_persist.delta_block_mask(image, live, block)
                    idx, rows, image = self._fetch_rows(name, image, live, mask, block)
                else:  # a first flush, a resized object, or a host value
                    value, flat = self._fetch(name, arr, as_bytes=True)
                    mask = None if resized else delta_persist.delta_block_mask(cur, value, block)
                dirty = blocks if mask is None else int(np.count_nonzero(mask))
                if tracing():
                    s.add(blocks=blocks, dirty_blocks=dirty)
            if on_device:
                self.stats.blocks_written += self.arena.merge(name, idx, rows, dirty)
            elif mask is None:
                self.stats.blocks_written += self.arena.rewrite(name, value)
                image = flat if _on_device(arr) else None
            else:
                self.stats.blocks_written += self.arena.flush(name, value, mask)
                image = None
            if image is not None:
                self._images[name] = (image, self.arena.peek(name))
        self.arena.save_manifest()
        self.stats.bytes_written += self.arena.file_bytes - file_bytes

    @staticmethod
    def _fetch(name: str, arr, as_bytes: bool) -> Tuple[np.ndarray, Optional[jax.Array]]:
        """The value on the host, row-major, and for a device value its flat
        device copy (its bytes where the copy is kept as the object's device
        image): fetched whole, in one copy (``flush.fetch``)."""
        if not _on_device(arr):
            return np.asarray(arr), None
        with span("flush.fetch", object=name, nbytes=arr.nbytes, whole=1):
            flat = (_flat_bytes if as_bytes else _flat_copy)(arr)
            return np.asarray(flat).view(arr.dtype).reshape(arr.shape), flat

    @staticmethod
    def _fetch_rows(name: str, image: jax.Array, live: jax.Array, mask: np.ndarray,
                    block: int):
        """Merge the blocks the mask marks from the flat device bytes ``live``
        into the device ``image``, and fetch the rows that hold them: the
        rows' indices, their bytes on the host (``uint8``, one row of
        ``k`` blocks each, any rows past the indices a gather's padding), and
        the new device image. A row is the fewest blocks that fill whole
        lanes; the gather's length is the dirty row count rounded up to a power
        of two (at most every row), so a session compiles it once per size
        (``flush.fetch``)."""
        block_elems = block // live.dtype.itemsize
        k = math.lcm(block_elems, LANES) // block_elems
        n_rows = -(-mask.size // k)
        by_row = np.pad(mask, (0, n_rows * k - mask.size)).reshape(n_rows, k)
        dirty = np.flatnonzero(by_row.any(axis=1))
        with span("flush.fetch", object=name, whole=0) as f:
            rows = np.zeros((0, k * block), np.uint8)
            if dirty.size:
                idx = np.full(min(1 << (dirty.size - 1).bit_length(), n_rows), dirty[-1], np.int32)
                idx[:dirty.size] = dirty
                merged, image = _merge_rows(image, live, idx, by_row[idx], block_elems=block_elems)
                rows = np.ascontiguousarray(merged).view(np.uint8).reshape(idx.size, k * block)
                image.block_until_ready()  # no device read outlives the flush
            if tracing():
                f.add(nbytes=rows.nbytes + mask.nbytes, rows=len(rows))
        return dirty, rows, image

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
        flat_init = {k: np.asarray(v) for k, v in flatten_state(init_state).items()}
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
