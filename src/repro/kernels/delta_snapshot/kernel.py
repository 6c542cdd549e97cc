"""Dirty-block detection kernel for EasyCrash delta flushes.

The paper's mechanism relies on CLWB being ~free for clean cache blocks; TPUs
have no dirty bit, so we *compute* it: compare the live shard against the
last-persisted snapshot at flush-block granularity and emit a per-block
changed mask.  The host then DMAs only dirty blocks (see
``repro.core.manager``).  Bandwidth-bound: one pass over 2x the shard bytes.

Layout (lane-dense): both operands arrive as 32-bit words in ``(rows, C)``
tiles, ``C = max(128, cols)`` lanes, where every flush block occupies
``cols`` consecutive words of one row (``cols`` divides 128 or is a multiple
of it), so one row holds ``k = C // cols`` blocks.  The kernel casts the
word-wise ``!=`` to 0/1 and sums each block's segment with one ``(k, C) x
(tr, C)^T`` segment-indicator matmul, which lands the per-block counts as a
lane-dense ``(k, tr)`` int32 tile: no bool reduction, no rank-1 block, no
relayout.  Counts are small integers, exact at any matmul precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: words per (rows, C) input tile: 1 MiB per operand buffer
TILE_WORDS = 1 << 18


def _delta_kernel(x_ref, p_ref, o_ref, *, cols: int):
    d = jnp.where(x_ref[...] != p_ref[...], 1.0, 0.0).astype(jnp.float32)
    k, c = o_ref.shape[0], d.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (k, c), 1)
    blk = jax.lax.broadcasted_iota(jnp.int32, (k, c), 0)
    seg = jnp.where(lane // cols == blk, 1.0, 0.0).astype(jnp.float32)
    counts = jax.lax.dot_general(
        seg, d, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[...] = jnp.where(counts > 0.0, 1, 0).astype(jnp.int32)


def tile_rows(rows: int, lanes: int) -> int:
    """Rows per grid tile: one tile when everything fits, else a multiple of
    128 (the output tile's lane dimension) near :data:`TILE_WORDS`."""
    tr = max(128, (TILE_WORDS // lanes) // 128 * 128)
    if rows <= tr:
        return -(-rows // 8) * 8
    return tr


def dirty_block_mask_words(
    x: jax.Array, prev: jax.Array, *, cols: int, interpret: bool = True,
) -> jax.Array:
    """x, prev: int32 ``(rows, C)`` word tiles, ``rows`` a multiple of
    :func:`tile_rows` -> int32 ``(k, rows)``; entry ``[j, r]`` flags block
    ``r * k + j`` (``k = C // cols``)."""
    rows, lanes = x.shape
    k = lanes // cols
    tr = tile_rows(rows, lanes)
    assert rows % tr == 0 and lanes % cols == 0, (rows, tr, lanes, cols)
    return pl.pallas_call(
        functools.partial(_delta_kernel, cols=cols),
        grid=(rows // tr,),
        in_specs=[
            pl.BlockSpec((tr, lanes), lambda i: (i, 0)),
            pl.BlockSpec((tr, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((k, tr), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, rows), jnp.int32),
        interpret=interpret,
    )(x, prev)
