"""Shared layers: norms, rotary embedding, MLPs, initializers."""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import with_logical
from .config import ModelConfig


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def normal_init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":  # Nemotron-4 squared ReLU
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name}")


# ------------------------------------------------------------------ rotary
def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions: (...,) int32 -> (cos, sin) of shape (..., head_dim/2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context stretched ``factor`` times."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """Inverse frequencies of ``dim``-wide rotary pairs under YaRN
    (:class:`~repro.models.config.YarnConfig`): the original frequencies for
    pairs that turn more than ``beta_fast`` times over the original context,
    those divided by ``factor`` for pairs that turn fewer than ``beta_slow``
    times, and a linear ramp between the two pair indices (floored and
    ceiled)."""
    def pair_index(rotations: float) -> float:
        return dim * math.log(yarn.original_max_position / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_index(yarn.beta_fast)), 0)
    high = min(math.ceil(pair_index(yarn.beta_slow)), dim - 1)
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / max(high - low, 1e-3), 0, 1)
    return (base / yarn.factor * ramp + base * (1 - ramp)).astype(np.float32)


def rope_angles_interleaved(positions: jax.Array, inv_freq: np.ndarray):
    """positions: (...,) -> (cos, sin) of shape (..., len(inv_freq))."""
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotary on adjacent pairs ``(x[2i], x[2i+1])`` (complex-number
    pairing, as DeepSeek-V2 rotates). x: (..., S, H, D); cos/sin: (S, D/2)."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape).astype(x.dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, D); cos/sin: (S, D/2) or broadcastable."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    # broadcast cos/sin over head axis: (S, 1, D/2)
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)


# --------------------------------------------------------------------- MLP
def mlp_params(cfg: ModelConfig, key, n: int, d_ff: Optional[int] = None) -> Dict:
    """Stacked gated-MLP params for ``n`` layers."""
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = d ** -0.5
    scale_out = ff ** -0.5
    return {
        "w_gate": normal_init(k1, (n, d, ff), scale_in, dt),
        "w_up": normal_init(k2, (n, d, ff), scale_in, dt),
        "w_down": normal_init(k3, (n, ff, d), scale_out, dt),
    }


def mlp_specs() -> Dict:
    return {
        "w_gate": (None, "fsdp", "ff"),
        "w_up": (None, "fsdp", "ff"),
        "w_down": (None, "ff", "fsdp"),
    }


def mlp_apply(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, d). Megatron-style: ff dim sharded, down-proj row-parallel."""
    act = activation_fn(cfg.activation)
    h = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = with_logical(act(h) * u, "batch", None, "ff")
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return with_logical(out, "batch", "seq", None)
