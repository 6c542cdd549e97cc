"""NVM arena: durable named images and their files.

The arena emulates NVM-as-main-memory in *app-direct* mode (paper §2.3):
a byte-addressable persistent region that survives crashes.  It holds one
row-major numpy array per named data object (the "NVM image"), optionally
backed by one file per object plus a manifest, written by the durable
replace protocol, so a killed process can reattach to the last images it
persisted.  The arena merges and writes what it is given; which blocks of
an object a flush writes is the caller's decision
(:class:`~repro.core.manager.EasyCrashManager`).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Optional

import numpy as np

from ..telemetry import span
from .blocks import DEFAULT_BLOCK_BYTES, obj_num_blocks, put_blocks, take_blocks
from .durable import durable_replace


class NVMArena:
    """Persistent store for named data objects at block granularity.

    Every image the arena holds is its own row-major (C-contiguous), writable
    array, so a masked flush merges its dirty blocks into it in place.
    """

    def __init__(
        self,
        block_bytes: int = DEFAULT_BLOCK_BYTES,
        backing_dir: Optional[str] = None,
    ):
        self.block_bytes = int(block_bytes)
        self.backing_dir = backing_dir
        self._store: Dict[str, np.ndarray] = {}
        #: bytes written to the objects' backing files, headers included
        self.file_bytes = 0
        if backing_dir:
            os.makedirs(backing_dir, exist_ok=True)

    # ------------------------------------------------------------------ values
    def names(self) -> Iterable[str]:
        return self._store.keys()

    def __contains__(self, name: str) -> bool:
        return name in self._store

    def get(self, name: str) -> np.ndarray:
        """Read the NVM image of an object (copy: loads survive app writes)."""
        return self._store[name].copy()

    def peek(self, name: str) -> Optional[np.ndarray]:
        """No-copy view of the current NVM image (delta-mask computation).

        Callers must not mutate the result, and a later flush of the object
        may update it in place; ``None`` if never persisted.
        """
        return self._store.get(name)

    def install(self, name: str, value: np.ndarray) -> None:
        """Install a full image (initialization / checkpoint restore path)."""
        self._store[name] = np.array(value, copy=True, order="C")
        self._persist_to_backing(name)

    # ------------------------------------------------------------ block writes
    def flush(self, name: str, live_value: np.ndarray, mask: np.ndarray) -> int:
        """EasyCrash persistence operation: merge the blocks ``mask`` marks
        dirty (one flag per block of the image) from ``live_value`` into the
        object's image, in place, and persist the image if any block moved.

        The image must exist at the value's byte size; a first flush, or an
        object that changed size, goes to :meth:`rewrite`.
        Returns the number of blocks written.
        """
        live_value = np.asarray(live_value)
        cur = self._image(name, live_value.nbytes)
        mask = np.asarray(mask, dtype=bool)
        nb = obj_num_blocks(cur, self.block_bytes)
        if mask.shape != (nb,):
            raise ValueError(f"{name!r}: mask of shape {mask.shape} for {nb} blocks")
        idx = np.flatnonzero(mask)
        return self.merge(name, idx, take_blocks(live_value, idx, self.block_bytes), idx.size)

    def merge(self, name: str, idx: np.ndarray, rows: np.ndarray, blocks: int) -> int:
        """Merge rows of bytes into the object's image, in place, and persist
        the image if any moved: row ``i`` of ``rows`` (``uint8``, each a whole
        number of blocks wide) covers the image's bytes from row index
        ``idx[i]``; rows past ``len(idx)`` are ignored
        (:func:`~repro.core.blocks.put_blocks`). ``blocks`` is the number of
        dirty blocks the rows carry, which is counted and returned."""
        cur = self._image(name)
        if idx.size:
            with span("arena.mix", object=name, blocks=int(blocks)):
                put_blocks(cur, idx, rows, rows.shape[1])
            self._persist_to_backing(name)
        return int(blocks)

    def _image(self, name: str, nbytes: Optional[int] = None) -> np.ndarray:
        """The image a masked flush merges into: it must exist, at ``nbytes``
        if given; a first flush, or an object that changed size, is a
        :meth:`rewrite`."""
        cur = self._store.get(name)
        if cur is None or (nbytes is not None and cur.nbytes != nbytes):
            size = "" if nbytes is None else f" of {nbytes} bytes"
            raise ValueError(f"{name!r}: no image{size} to merge into; "
                             "a first or resized flush is a rewrite")
        return cur

    def rewrite(self, name: str, live_value: np.ndarray) -> int:
        """Persistence operation that writes every block of the object: for
        a first flush, a resized object, and an object that each step
        rewrites whole, where a mask would find every block dirty. Returns
        the blocks written."""
        self._store[name] = np.array(live_value, copy=True, order="C")
        self._persist_to_backing(name)
        return obj_num_blocks(live_value, self.block_bytes)

    # -------------------------------------------------------------- durability
    # Backing files follow the shared durable-replace protocol
    # (:mod:`repro.core.durable`): ``reattach`` must never see an empty or
    # torn image, even after power loss mid-rename.
    def _backing_path(self, name: str) -> str:
        safe = name.replace("/", "__")
        return os.path.join(self.backing_dir, f"{safe}.npy")  # type: ignore[arg-type]

    def _persist_to_backing(self, name: str) -> None:
        if not self.backing_dir:
            return
        path = self._backing_path(name)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            with span("arena.write", object=name) as s:
                np.save(f, self._store[name])
                f.flush()
                nbytes = f.tell()
                s.add(nbytes=nbytes)
            with span("arena.fsync", object=name):
                os.fsync(f.fileno())
        with span("arena.rename", object=name):
            durable_replace(tmp, path)
        self.file_bytes += nbytes

    def save_manifest(self) -> None:
        if not self.backing_dir:
            return
        with span("arena.manifest"):
            manifest = {
                "block_bytes": self.block_bytes,
                "objects": {k: str(v.dtype) for k, v in self._store.items()},
            }
            path = os.path.join(self.backing_dir, "manifest.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            durable_replace(tmp, path)

    @classmethod
    def reattach(cls, backing_dir: str) -> "NVMArena":
        """Reload a persisted arena after a crash (the restart path)."""
        path = os.path.join(backing_dir, "manifest.json")
        with open(path) as f:
            manifest = json.load(f)
        arena = cls(block_bytes=manifest["block_bytes"], backing_dir=backing_dir)
        objects = manifest["objects"]
        if isinstance(objects, list):  # legacy manifests without dtypes
            objects = {name: None for name in objects}
        for name, dtype_s in objects.items():
            arr = np.load(arena._backing_path(name))
            if dtype_s is not None and str(arr.dtype) != dtype_s:
                want = np.dtype(dtype_s)
                # np.load round-trips extension dtypes (bfloat16) as void
                if arr.dtype.kind == "V" and arr.dtype.itemsize == want.itemsize:
                    arr = arr.view(want)
                else:
                    arr = arr.astype(want)
            arena._store[name] = np.asarray(arr, order="C")  # a no-op for the C files flush writes
        return arena
