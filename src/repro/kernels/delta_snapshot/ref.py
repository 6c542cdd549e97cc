"""numpy/jnp oracle for dirty-block detection."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dirty_block_mask_reference(x, prev):
    """x, prev: (n_blocks, block_elems) -> int32 (n_blocks,); a block is
    dirty iff any element's bits differ (so NaN == NaN, -0.0 != +0.0)."""
    bits = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
    xb = jax.lax.bitcast_convert_type(x, bits)
    pb = jax.lax.bitcast_convert_type(prev, bits)
    return (xb != pb).any(axis=1).astype(jnp.int32)
