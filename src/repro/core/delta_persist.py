"""Incremental ("delta") persistence: Pallas dirty-block masks for the arena.

Bridges :mod:`repro.kernels.delta_snapshot` to :class:`repro.core.arena.NVMArena`.
The arena reasons in *bytes* (cache blocks of ``block_bytes``); the kernel
compares element streams of one dtype.  Host values are compared as flat
``uint8`` views with ``block_elems = block_bytes``; device values, flat and
row-major, where they are, with ``block_elems = block_bytes / itemsize`` (the
manager hands the device its values' bytes, so the device mask runs the very
program the host mask compiles for an object of that size).
Either way the kernel's block boundary coincides exactly with the arena's,
and the kernel compares bits, so the mask is bit-for-bit the mask
:func:`repro.core.blocks.block_diff_mask` computes: a delta flush writes a
byte-identical NVM image to a whole-object flush (asserted by the
differential tests in ``tests/test_kernel_differential.py``).

On a TPU the kernel is compiled; elsewhere Pallas interprets it.  The
contract (and therefore the persisted image) is the same either way.
"""
from __future__ import annotations

import jax
import numpy as np

from ..kernels.delta_snapshot import dirty_block_mask
from .blocks import DEFAULT_BLOCK_BYTES, _as_byte_view


def delta_block_mask(
    cur,
    live,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
) -> np.ndarray:
    """Per-block "changed" mask between the NVM image and the live value.

    Same contract as :func:`repro.core.blocks.block_diff_mask` (host bool
    ``(n_blocks,)``, final partial block is a real block, padding never
    reads as dirty) — computed by the ``delta_snapshot`` kernel. ``cur`` and
    ``live`` are host arrays, compared byte for byte, or both device arrays
    of one flat shape and dtype whose itemsize divides ``block_bytes``,
    compared where they are; only the mask comes back to the host.
    """
    if isinstance(cur, jax.Array) and isinstance(live, jax.Array):
        if cur.shape != live.shape or cur.dtype != live.dtype or live.ndim != 1:
            raise ValueError(f"size mismatch: {cur.shape}/{cur.dtype} vs "
                             f"{live.shape}/{live.dtype}, both flat")
        if block_bytes % live.dtype.itemsize:
            raise ValueError(f"a {live.dtype} element does not divide {block_bytes}-byte blocks")
        x, prev, block_elems = live, cur, block_bytes // live.dtype.itemsize
    else:
        prev, x = _as_byte_view(np.asarray(cur)), _as_byte_view(np.asarray(live))
        if prev.size != x.size:
            raise ValueError("size mismatch")
        block_elems = block_bytes
    if x.size == 0:
        return np.zeros((0,), dtype=bool)
    return np.asarray(dirty_block_mask(x, prev, block_elems=int(block_elems)) != 0)
