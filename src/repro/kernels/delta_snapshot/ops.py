"""Public dirty-block op: flat arrays in, per-block mask out."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import dirty_block_mask_words, tile_rows

DEFAULT_BLOCK_ELEMS = 256


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _block_cols(wpb: int) -> int:
    """Words a block occupies in the kernel layout: ``wpb`` rounded up to a
    divisor of 128 (a power of two) or to a multiple of 128."""
    if wpb > 128:
        return -(-wpb // 128) * 128
    c = 1
    while c < wpb:
        c <<= 1
    return c


def _words(a: jax.Array, packed: bool) -> jax.Array:
    """Flat array -> flat int32 words holding its bits: consecutive elements
    packed four bytes to a word, or each element zero-extended to one."""
    isz = a.dtype.itemsize
    if not packed:
        return jax.lax.bitcast_convert_type(a, jnp.dtype(f"uint{8 * isz}")).astype(jnp.int32)
    if isz < 4:
        a = a.reshape(-1, 4 // isz)
    return jax.lax.bitcast_convert_type(a, jnp.int32).reshape(-1)


def _tiles(a: jax.Array, nb: int, block_elems: int, packed: bool, wpb: int,
           cols: int, nb_pad: int) -> jax.Array:
    """Flat array -> int32 ``(rows, lanes)`` kernel tiles of ``nb_pad``
    blocks of ``cols`` words.  Both operands are zero-padded identically, so
    padding words and padding blocks never differ."""
    lanes = max(128, cols)
    a = a.reshape(-1)
    if cols == wpb:  # blocks fill their columns: one pad, at the end
        a = jnp.pad(a, (0, nb_pad * block_elems - a.shape[0]))
        return _words(a, packed).reshape(-1, lanes)
    a = jnp.pad(a, (0, nb * block_elems - a.shape[0]))
    w = _words(a, packed).reshape(nb, wpb)
    return jnp.pad(w, ((0, nb_pad - nb), (0, cols - wpb))).reshape(-1, lanes)


@functools.partial(jax.jit, static_argnames=("block_elems",))
def dirty_block_mask(x, prev, *, block_elems: int = DEFAULT_BLOCK_ELEMS):
    """x, prev: same-shape arrays -> int32 (n_blocks,) changed mask.

    A block is ``block_elems`` consecutive elements of the flattened arrays
    (the last one may be partial) and is flagged iff any of its bytes
    differ — :func:`repro.core.blocks.block_diff_mask`'s contract.
    """
    nb = -(-x.size // block_elems)
    if nb == 0:
        return jnp.zeros((0,), jnp.int32)
    block_bytes = block_elems * x.dtype.itemsize
    packed = block_bytes % 4 == 0
    wpb = block_bytes // 4 if packed else block_elems
    cols = _block_cols(wpb)
    lanes = max(128, cols)
    k = lanes // cols
    rows = -(-nb // k)
    tr = tile_rows(rows, lanes)
    nb_pad = -(-rows // tr) * tr * k
    xw = _tiles(x, nb, block_elems, packed, wpb, cols, nb_pad)
    pw = _tiles(prev, nb, block_elems, packed, wpb, cols, nb_pad)
    out = dirty_block_mask_words(xw, pw, cols=cols, interpret=not _on_tpu())
    return out.T.reshape(-1)[:nb]
