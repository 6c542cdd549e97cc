"""Incremental ("delta") persistence: Pallas dirty-block masks for the arena.

Bridges :mod:`repro.kernels.delta_snapshot` to :class:`repro.core.arena.NVMArena`.
The arena reasons in *bytes* (cache blocks of ``block_bytes``); the kernel
compares element streams.  We therefore run the kernel over flat ``uint8``
views with ``block_elems = block_bytes``, which makes the kernel's block
boundary coincide exactly with the arena's — the resulting mask is
bit-for-bit the mask :func:`repro.core.blocks.block_diff_mask` computes, so a
delta flush writes a byte-identical NVM image to a whole-object flush
(asserted by the differential test in ``tests/test_kernel_differential.py``).

On a TPU the kernel is compiled; elsewhere Pallas interprets it.  The
contract (and therefore the persisted image) is the same either way.
"""
from __future__ import annotations

import numpy as np

from ..kernels.delta_snapshot import dirty_block_mask
from .blocks import DEFAULT_BLOCK_BYTES, _as_byte_view


def delta_block_mask(
    cur: np.ndarray,
    live: np.ndarray,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
) -> np.ndarray:
    """Per-block "changed" mask between the NVM image and the live value.

    Same contract as :func:`repro.core.blocks.block_diff_mask` (bool
    ``(n_blocks,)``, final partial block is a real block, padding never reads
    as dirty) — computed by the ``delta_snapshot`` kernel.
    """
    av = _as_byte_view(np.asarray(cur))
    bv = _as_byte_view(np.asarray(live))
    if av.size != bv.size:
        raise ValueError("size mismatch")
    if av.size == 0:
        return np.zeros((0,), dtype=bool)
    mask = np.asarray(dirty_block_mask(bv, av, block_elems=int(block_bytes)))
    return mask.astype(bool)
