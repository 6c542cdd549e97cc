"""GQA attention: train/prefill (full-sequence) and decode (KV cache) paths.

Sharding: query heads go to "heads" (model axis); K/V projections replicate
when n_kv_heads doesn't divide the TP degree (the GQA<TP case) and the decode
KV cache is then sequence-sharded ("kv_seq") instead of head-sharded.
Supports causal and local-window (RecurrentGemma) masking, rotary or no
position embedding (``cfg.rope``), and a configured score scale.

The full-sequence path can route through the Pallas flash-attention kernel
(``impl="pallas"``) on TPU; the einsum reference is the default and the
numerically-identical oracle.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import with_logical
from .config import ModelConfig
from .layers import apply_rope, dtype_of, normal_init, rope_angles


def attn_params(cfg: ModelConfig, key, n: int) -> Dict:
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    so = (hq * dh) ** -0.5
    return {
        "wq": normal_init(k1, (n, d, hq * dh), s, dt),
        "wk": normal_init(k2, (n, d, hkv * dh), s, dt),
        "wv": normal_init(k3, (n, d, hkv * dh), s, dt),
        "wo": normal_init(k4, (n, hq * dh, d), so, dt),
    }


def attn_specs(cfg: ModelConfig, tp: int = 16) -> Dict:
    kv_sharded = cfg.n_kv_heads % tp == 0
    kv = "heads" if kv_sharded else None
    return {
        "wq": (None, "fsdp", "heads"),
        "wk": (None, "fsdp", kv),
        "wv": (None, "fsdp", kv),
        "wo": (None, "heads", "fsdp"),
    }


def score_scale(cfg: ModelConfig) -> float:
    """What attention scores are multiplied by: the configuration's
    ``attn_scale``, else ``head_dim ** -0.5``."""
    return cfg.attn_scale if cfg.attn_scale is not None else cfg.head_dim ** -0.5


def _split_heads(x: jax.Array, n_heads: int, d_head: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, d_head)


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, Hkv*groups, D) for GQA."""
    if groups == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, groups, d)).reshape(b, s, h * groups, d)


def _mask_bias(seq_q: int, seq_k: int, offset: int, window: Optional[int], dtype) -> jax.Array:
    """(seq_q, seq_k) additive mask; q position i attends k position j iff
    j <= i+offset and (window is None or j > i+offset-window)."""
    qpos = jnp.arange(seq_q)[:, None] + offset
    kpos = jnp.arange(seq_k)[None, :]
    ok = kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, -1e30).astype(dtype)


def attention_full(
    p: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    window: Optional[int] = None,
    impl: str = "reference",
) -> jax.Array:
    """Full-sequence causal attention.  x: (B, S, d); positions: (S,)."""
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wq"]), hq, dh)
    k = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wk"]), hkv, dh)
    v = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wv"]), hkv, dh)
    if cfg.rope:
        cos, sin = rope_angles(positions, dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = with_logical(q, "batch", None, "heads", None)
    k = with_logical(k, "batch", None, "kv_heads" if hkv % 8 == 0 else None, None)

    if impl != "reference" and cfg.attn_scale is not None:
        # the kernels scale by head_dim ** -0.5; fold the difference into q
        q = q * jnp.asarray(cfg.attn_scale * dh ** 0.5, q.dtype)
    if impl == "pallas":
        from ..kernels.flash_attention.ops import flash_attention

        out = flash_attention(q, _repeat_kv(k, hq // hkv), _repeat_kv(v, hq // hkv),
                              causal=True, window=window)
    elif impl == "chunked":
        out = _attention_chunked(q, _repeat_kv(k, hq // hkv), _repeat_kv(v, hq // hkv),
                                 window=window)
    else:
        k = _repeat_kv(k, hq // hkv)
        v = _repeat_kv(v, hq // hkv)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * score_scale(cfg)
        bias = _mask_bias(q.shape[1], k.shape[1], 0, window, jnp.float32)
        probs = jax.nn.softmax(scores.astype(jnp.float32) + bias, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = out.reshape(x.shape[0], x.shape[1], hq * dh)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return with_logical(y, "batch", "seq", None)


def _attention_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, window: Optional[int] = None, chunk: int = 512,
) -> jax.Array:
    """Flash-style causal attention as a ``lax.scan`` over KV chunks.

    Never materializes the (S x S) score matrix — per scan step only a
    (B, H, S, chunk) tile exists, so HBM traffic drops by ~S/chunk relative
    to the naive einsum path.  This is the XLA-portable analogue of the
    Pallas ``flash_attention`` kernel (same online-softmax recurrence), used
    where Pallas cannot compile (CPU dry-runs) and as the §Perf
    beyond-baseline attention for the memory-bound archs.
    """
    b, s, h, d = q.shape
    c = min(chunk, s)
    assert s % c == 0, (s, c)
    scale = d ** -0.5
    nk = s // c
    qf = q.astype(jnp.float32) * scale
    kc = k.astype(jnp.float32).reshape(b, nk, c, h, d)
    vc = v.astype(jnp.float32).reshape(b, nk, c, h, d)
    qpos = jnp.arange(s)

    def body(carry, inputs):
        m, l, acc = carry
        kci, vci, ik = inputs
        kpos = ik * c + jnp.arange(c)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qf, kci)
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(ok[None, None], sc, -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        p = jnp.where(ok[None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vci)
        return (m_new, l, acc), None

    m0 = jnp.full((b, h, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    acc0 = jnp.zeros((b, h, s, d), jnp.float32)
    xs = (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nk))
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def init_kv_cache(cfg: ModelConfig, n_layers: int, batch: int, max_len: int,
                  window: Optional[int] = None) -> Dict:
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    length = min(max_len, window) if window else max_len
    dt = dtype_of(cfg)
    return {
        "k": jnp.zeros((n_layers, batch, length, hkv, dh), dt),
        "v": jnp.zeros((n_layers, batch, length, hkv, dh), dt),
    }


def kv_cache_specs(cfg: ModelConfig, tp: int = 16) -> Dict:
    if cfg.n_kv_heads % tp == 0:
        spec = (None, "batch", None, "kv_heads", None)
    else:
        spec = (None, "batch", "kv_seq", None, None)
    return {"k": spec, "v": spec}


def attention_decode(
    p: Dict,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cfg: ModelConfig,
    t: jax.Array,
    window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode.  x: (B, 1, d); cache: (B, L, Hkv, dh); t: scalar
    position of the new token.  Returns (y, new_cache_k, new_cache_v).

    With a window, the cache is a rolling buffer of size W and the slot is
    t mod W; otherwise the cache is absolute-addressed.
    """
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b = x.shape[0]
    length = cache_k.shape[1]
    q = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wq"]), hq, dh)
    k = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wk"]), hkv, dh)
    v = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wv"]), hkv, dh)
    if cfg.rope:
        cos, sin = rope_angles(t[None], dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    slot = (t % length) if window else t
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=1)

    kk = _repeat_kv(cache_k, hq // hkv)
    vv = _repeat_kv(cache_v, hq // hkv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * score_scale(cfg)  # (B, H, 1, L)
    kpos = jnp.arange(length)
    if window:
        valid = (kpos <= t % length) | (t >= length)  # rolling buffer: all valid once full
    else:
        valid = kpos <= t
    bias = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)
    probs = jax.nn.softmax(scores.astype(jnp.float32) + bias[None, None, None, :], axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(x.dtype), vv)
    out = out.reshape(b, 1, hq * dh)
    y = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    return with_logical(y, "batch", None, None), cache_k, cache_v
