"""GQA attention: train/prefill (full-sequence) and decode (KV cache) paths;
and multi-head latent attention (MLA, DeepSeek-V2), whose decode cache is a
latent and one shared rotary key per position.

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
from .layers import (
    apply_rope,
    apply_rope_interleaved,
    dtype_of,
    normal_init,
    rms_norm,
    rope_angles,
    rope_angles_interleaved,
    yarn_inv_freq,
    yarn_mscale,
)


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


# ------------------------------------------------------ latent attention (MLA)
# DeepSeek-V2: q = x Wq, per head [nope | rope]; [c | k_pe] = x Wkv_a, the
# latent c RMS-normed, k_pe one rotary key shared by every head; per head
# [k_nope | v] = c Wkv_b; scores q_nope.k_nope + q_pe.k_pe. Rotary turns
# adjacent pairs, at YaRN's frequencies where the configuration scales them.
def mla_params(cfg: ModelConfig, key, n: int) -> Dict:
    a, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    dt = dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": normal_init(k1, (n, d, h * a.qk_head_dim), d ** -0.5, dt),
        "wkv_a": normal_init(k2, (n, d, a.kv_lora_rank + a.qk_rope_head_dim), d ** -0.5, dt),
        "kv_norm": jnp.zeros((n, a.kv_lora_rank), dt),
        "wkv_b": normal_init(k3, (n, a.kv_lora_rank, h * (a.qk_nope_head_dim + a.v_head_dim)),
                             a.kv_lora_rank ** -0.5, dt),
        "wo": normal_init(k4, (n, h * a.v_head_dim, d), (h * a.v_head_dim) ** -0.5, dt),
    }


def mla_specs() -> Dict:
    return {
        "wq": (None, "fsdp", "heads"),
        "wkv_a": (None, "fsdp", None),
        "kv_norm": (None, None),
        "wkv_b": (None, None, "heads"),
        "wo": (None, "heads", "fsdp"),
    }


def mla_score_scale(cfg: ModelConfig) -> float:
    """DeepSeek-V2's softmax scale: ``qk_head_dim ** -0.5``, times YaRN's
    ``mscale(factor, mscale_all_dim)`` squared where the rotary is scaled."""
    y = cfg.yarn
    m = yarn_mscale(y.factor, y.mscale_all_dim) if y is not None and y.mscale_all_dim else 1.0
    return cfg.mla.qk_head_dim ** -0.5 * m * m


def mla_inv_freq(cfg: ModelConfig):
    dim = cfg.mla.qk_rope_head_dim
    if cfg.yarn is not None:
        return yarn_inv_freq(dim, cfg.rope_theta, cfg.yarn)
    return 1.0 / cfg.rope_theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)


def _mla_inputs(p: Dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """x: (B, S, d) -> q's no-rotary part (B, S, H, nope), q's rotary part
    (B, S, H, rope), the normed latent (B, S, rank) and the rotary key
    (B, S, rope), the last two as the cache holds them."""
    a = cfg.mla
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, s, cfg.n_heads, a.qk_head_dim)
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = rms_norm(kv[..., :a.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    cos, sin = rope_angles_interleaved(positions, mla_inv_freq(cfg))
    q_pe = apply_rope_interleaved(q[..., a.qk_nope_head_dim:], cos, sin)
    k_pe = apply_rope_interleaved(kv[..., None, a.kv_lora_rank:], cos, sin)[..., 0, :]
    return q[..., :a.qk_nope_head_dim], q_pe, c_kv, k_pe


def _causal_by_query_chunks(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                            chunk: int) -> jax.Array:
    """Causal softmax attention, ``chunk`` queries at a time against every
    key, so that no (S x S) score matrix exists at once. q, k: (B, S, H, Dk);
    v: (B, S, H, Dv) -> (B, S, H, Dv)."""
    b, s, h, _ = q.shape
    c = min(chunk, s)
    n = -(-s // c)
    qc = jnp.pad(q, ((0, 0), (0, n * c - s), (0, 0), (0, 0))).reshape(b, n, c, h, -1)
    kpos = jnp.arange(s)

    def one(args):
        qi, i = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, preferred_element_type=jnp.float32) * scale
        ok = kpos[None, :] <= (i * c + jnp.arange(c))[:, None]
        probs = jax.nn.softmax(jnp.where(ok, sc, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)

    out = jax.lax.map(one, (jnp.moveaxis(qc, 1, 0), jnp.arange(n)))  # (n, B, c, H, Dv)
    return jnp.moveaxis(out, 0, 1).reshape(b, n * c, h, -1)[:, :s]


def mla_full(p: Dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
             chunk: int = 128) -> Tuple[jax.Array, Dict]:
    """Full-sequence causal MLA, with each head's K and V expanded from the
    latent, ``chunk`` queries at a time. x: (B, S, d). Returns (y, the
    positions' decode cache ``{"c_kv", "k_pe"}``)."""
    a, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    q_nope, q_pe, c_kv, k_pe = _mla_inputs(p, x, cfg, positions)
    kv = jnp.einsum("bsr,rh->bsh", c_kv, p["wkv_b"]).reshape(
        b, s, h, a.qk_nope_head_dim + a.v_head_dim)
    k = jnp.concatenate([kv[..., :a.qk_nope_head_dim],
                         jnp.broadcast_to(k_pe[:, :, None], (b, s, h, a.qk_rope_head_dim))], -1)
    q = with_logical(jnp.concatenate([q_nope, q_pe], -1), "batch", None, "heads", None)
    out = _causal_by_query_chunks(q, k, kv[..., a.qk_nope_head_dim:], mla_score_scale(cfg), chunk)
    y = jnp.einsum("bsh,hd->bsd", out.reshape(b, s, h * a.v_head_dim), p["wo"])
    return with_logical(y, "batch", "seq", None), {"c_kv": c_kv, "k_pe": k_pe}


def init_latent_cache(cfg: ModelConfig, n_layers: int, batch: int, max_len: int) -> Dict:
    a = cfg.mla
    dt = dtype_of(cfg)
    return {
        "c_kv": jnp.zeros((n_layers, batch, max_len, a.kv_lora_rank), dt),
        "k_pe": jnp.zeros((n_layers, batch, max_len, a.qk_rope_head_dim), dt),
    }


def latent_cache_specs() -> Dict:
    spec = (None, "batch", None, None)
    return {"c_kv": spec, "k_pe": spec}


def mla_decode(p: Dict, x: jax.Array, c_kv: jax.Array, k_pe: jax.Array, cfg: ModelConfig,
               t: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token MLA over the latent cache, absorbed: ``Wkv_b``'s K half is
    folded into the query and its V half applied to the weighted sum of
    latents, so no per-head K or V is formed. x: (B, 1, d); c_kv: (B, L,
    rank); k_pe: (B, L, rope); t: the new token's position. Returns (y,
    c_kv, k_pe) with position ``t`` written."""
    a, h = cfg.mla, cfg.n_heads
    b = x.shape[0]
    q_nope, q_pe, c_new, k_new = _mla_inputs(p, x, cfg, t[None])
    c_kv = jax.lax.dynamic_update_slice_in_dim(c_kv, c_new, t, axis=1)
    k_pe = jax.lax.dynamic_update_slice_in_dim(k_pe, k_new, t, axis=1)
    w = p["wkv_b"].reshape(a.kv_lora_rank, h, a.qk_nope_head_dim + a.v_head_dim)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w[..., :a.qk_nope_head_dim])
    sc = (jnp.einsum("bqhr,bkr->bhqk", q_lat, c_kv, preferred_element_type=jnp.float32)
          + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe, preferred_element_type=jnp.float32))
    valid = jnp.arange(c_kv.shape[1]) <= t
    probs = jax.nn.softmax(jnp.where(valid, sc * mla_score_scale(cfg), -1e30), axis=-1)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", probs.astype(c_kv.dtype), c_kv)
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat, w[..., a.qk_nope_head_dim:])
    y = jnp.einsum("bsh,hd->bsd", out.reshape(b, 1, h * a.v_head_dim), p["wo"])
    return with_logical(y, "batch", None, None), c_kv, k_pe
