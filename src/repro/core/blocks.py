"""Block-granularity utilities.

EasyCrash reasons about persistence at *cache-block* granularity (64 B on
x86).  On TPU the analogous unit is the flush block used by the
``delta_snapshot`` kernel.  Everything in :mod:`repro.core` that mixes old and
new values, computes inconsistency rates or counts NVM writes does so in
units of blocks via these helpers.

Arrays are treated as flat byte streams; the final (possibly partial) block
is a real block (the paper's objects are not block-aligned either).
"""
from __future__ import annotations

import numpy as np

DEFAULT_BLOCK_BYTES = 64


def num_blocks(nbytes: int, block_bytes: int = DEFAULT_BLOCK_BYTES) -> int:
    """Number of cache blocks spanned by an object of ``nbytes`` bytes."""
    if nbytes <= 0:
        return 0
    return -(-nbytes // block_bytes)


def obj_nbytes(arr: np.ndarray) -> int:
    return int(np.asarray(arr).nbytes)


def obj_num_blocks(arr: np.ndarray, block_bytes: int = DEFAULT_BLOCK_BYTES) -> int:
    return num_blocks(obj_nbytes(arr), block_bytes)


def _as_byte_view(arr: np.ndarray) -> np.ndarray:
    """Flat uint8 view of an array (no copy)."""
    a = np.ascontiguousarray(arr)
    return a.view(np.uint8).reshape(-1)


def take_blocks(src: np.ndarray, idx: np.ndarray,
                block_bytes: int = DEFAULT_BLOCK_BYTES) -> np.ndarray:
    """The blocks ``idx`` (ascending) of ``src``'s bytes as ``uint8`` rows of
    ``(len(idx), block_bytes)``; a partial final block is zero-padded."""
    s = _as_byte_view(src)
    full = s.size // block_bytes
    rows = np.empty((idx.size, block_bytes), np.uint8)
    k = idx.size
    if k and idx[-1] == full:  # the partial final block
        k -= 1
        rows[k] = 0
        rows[k, :s.size - full * block_bytes] = s[full * block_bytes:]
    np.take(s[:full * block_bytes].reshape(full, block_bytes), idx[:k], axis=0, out=rows[:k])
    return rows


def put_blocks(
    dst: np.ndarray,
    idx: np.ndarray,
    rows: np.ndarray,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
) -> None:
    """In-place blockwise merge, the one way blocks enter an image: write row
    ``i`` of ``rows`` (``uint8``, ``block_bytes`` wide) into block ``idx[i]``
    of ``dst``, which must be C-contiguous and writable. ``idx`` ascends;
    rows past ``len(idx)`` (a gather's padding) are ignored, and a partial
    final block takes the head of its row."""
    if not (dst.flags.c_contiguous and dst.flags.writeable):
        raise ValueError("put_blocks needs a C-contiguous, writable destination")
    nb = obj_num_blocks(dst, block_bytes)
    if rows.ndim != 2 or rows.shape[0] < idx.size or rows.shape[1] != block_bytes:
        raise ValueError(f"{idx.size} blocks of {block_bytes} bytes from rows {rows.shape}")
    if idx.size == 0:
        return
    if idx[0] < 0 or idx[-1] >= nb:
        raise ValueError(f"block index out of range for {nb} blocks")
    d = dst.reshape(-1).view(np.uint8)
    full = d.size // block_bytes
    k = idx.size
    if idx[-1] == full:  # the partial final block
        k -= 1
        d[full * block_bytes:] = rows[k, :d.size - full * block_bytes]
    d[:full * block_bytes].reshape(full, block_bytes)[idx[:k]] = rows[:k]


def mix_blocks_into(
    dst: np.ndarray,
    src: np.ndarray,
    new_block_mask: np.ndarray,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
) -> None:
    """In-place blockwise merge: copy the blocks ``new_block_mask`` marks from
    ``src`` into ``dst``, which must be C-contiguous and writable.

    The marked blocks move as rows (:func:`take_blocks`, :func:`put_blocks`);
    a mask with every block set is one copy of the whole image.
    """
    src = np.asarray(src)
    if dst.shape != src.shape or dst.dtype != src.dtype:
        raise ValueError(f"mix_blocks shape/dtype mismatch: {dst.shape}/{dst.dtype} vs {src.shape}/{src.dtype}")
    nb = obj_num_blocks(dst, block_bytes)
    mask = np.asarray(new_block_mask, dtype=bool)
    if mask.shape != (nb,):
        raise ValueError(f"mask must have {nb} blocks, got {mask.shape}")
    if not (dst.flags.c_contiguous and dst.flags.writeable):
        raise ValueError("mix_blocks_into needs a C-contiguous, writable destination")
    idx = np.flatnonzero(mask)
    if 0 < idx.size == nb:
        np.copyto(dst.reshape(-1).view(np.uint8), _as_byte_view(src))
        return
    put_blocks(dst, idx, take_blocks(src, idx, block_bytes), block_bytes)


def mix_blocks(
    old: np.ndarray,
    new: np.ndarray,
    new_block_mask: np.ndarray,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
) -> np.ndarray:
    """Blockwise select: where ``new_block_mask[b]`` take ``new``, else ``old``.

    This is the post-crash NVM image constructor: persisted blocks carry the
    new value, lost (dirty-in-cache) blocks retain the stale one. ``old`` is
    not modified; see :func:`mix_blocks_into` for the in-place merge.
    """
    out = np.array(old, order="C", copy=True)
    mix_blocks_into(out, new, new_block_mask, block_bytes)
    return out


def inconsistent_rate(
    image: np.ndarray,
    truth: np.ndarray,
) -> float:
    """Fraction of *bytes* in ``image`` that differ from ``truth``.

    Matches NVCT's "data inconsistent rate": dirty (lost) bytes divided by
    the object size.
    """
    a = _as_byte_view(np.asarray(image))
    b = _as_byte_view(np.asarray(truth))
    if a.size != b.size:
        raise ValueError("size mismatch")
    if a.size == 0:
        return 0.0
    return float(np.count_nonzero(a != b)) / a.size


def block_diff_mask(
    a: np.ndarray,
    b: np.ndarray,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
) -> np.ndarray:
    """Per-block "changed" mask between two same-shaped arrays.

    CPU reference for the ``delta_snapshot`` Pallas kernel: a block is dirty
    iff any byte within it differs.
    """
    av = _as_byte_view(np.asarray(a))
    bv = _as_byte_view(np.asarray(b))
    if av.size != bv.size:
        raise ValueError("size mismatch")
    nb = num_blocks(av.size, block_bytes)
    if nb == 0:
        return np.zeros((0,), dtype=bool)
    diff = av != bv
    pad = nb * block_bytes - av.size
    if pad:
        diff = np.concatenate([diff, np.zeros(pad, dtype=bool)])
    return diff.reshape(nb, block_bytes).any(axis=1)
