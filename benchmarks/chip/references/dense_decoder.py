"""Plain reference of a dense decoder-only LM, in float32.

Independent of the code under test: it imports nothing from ``src/``.
It follows the architecture as the configuration file states it
(pre-norm RMSNorm with ``1 + gamma`` scale, rotary embedding on the whole
head, multi-head attention, SwiGLU MLP, untied output head) and makes its
own weights from the seed, by the recipe the configuration file names
(``init``): the same draws the served program makes, so that the two see
the same model.

Two precisions: ``"f32"`` (every matmul at ``HIGHEST``, the reference) and
``"fp8"`` (every matmul operand and the K/V cache rounded to float8 e4m3,
accumulated in float32: the lower-precision control).

Layer by layer under ``lax.scan``, so that the peak is one layer's
activations plus the bf16 weights.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, cfg: Dict) -> "Dims":
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            d_model=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            head_dim=int(cfg["head_dim"]),
            d_ff=int(cfg["intermediate_size"]),
            vocab=int(cfg["vocab_size"]),
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=float(cfg["layer_norm_eps"]),
        )


# ------------------------------------------------------------------ weights
def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)


def init_weights(d: Dims, seed: int) -> Dict:
    """bf16 weights from the seed, as the configuration's ``init`` recipe
    draws them: key split into (embed, unembed, unused, layers); the layer
    key split into (mix, ffn, norm); mix into (wq, wk, wv, wo); ffn into
    (gate, up, down). Normal draws in float32 times the scale, rounded to
    bfloat16; norms 0.

    Drawn one operation at a time, not in one jitted program: fused, XLA
    folds the scale into the draw's own constants and rounds some weights
    differently from the same recipe run op by op."""
    n, dm, hd, ff = d.layers, d.d_model, d.heads * d.head_dim, d.d_ff
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    (layer_key,) = jax.random.split(keys[3], 1)
    k_mix, k_ffn, _ = jax.random.split(layer_key, 3)
    k1, k2, k3, k4 = jax.random.split(k_mix, 4)
    f1, f2, f3 = jax.random.split(k_ffn, 3)
    zeros = jnp.zeros((n, dm), jnp.bfloat16)
    return {
        "embed": _normal(keys[0], (d.vocab, dm), 1.0),
        "unembed": _normal(keys[1], (dm, d.vocab), dm ** -0.5),
        "final_norm": jnp.zeros((dm,), jnp.bfloat16),
        "layers": {
            "norm1": zeros,
            "norm2": zeros,
            "wq": _normal(k1, (n, dm, hd), dm ** -0.5),
            "wk": _normal(k2, (n, dm, hd), dm ** -0.5),
            "wv": _normal(k3, (n, dm, hd), dm ** -0.5),
            "wo": _normal(k4, (n, hd, dm), hd ** -0.5),
            "w_gate": _normal(f1, (n, dm, ff), dm ** -0.5),
            "w_up": _normal(f2, (n, dm, ff), dm ** -0.5),
            "w_down": _normal(f3, (n, ff, dm), ff ** -0.5),
        },
    }


# ------------------------------------------------------------------ forward
def _round(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x.astype(jnp.float32)


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision), precision=HIGHEST)


def _rms(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))


def _rope(x, positions, theta):
    """x: (B, S, H, D); rotate the two halves of every head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(d: Dims, precision: str, x, w):
    b, s, _ = x.shape
    positions = jnp.arange(s)
    h = _rms(x, w["norm1"], d.norm_eps)
    q = _mm("bsd,dh->bsh", h, w["wq"], precision).reshape(b, s, d.heads, d.head_dim)
    k = _mm("bsd,dh->bsh", h, w["wk"], precision).reshape(b, s, d.heads, d.head_dim)
    v = _mm("bsd,dh->bsh", h, w["wv"], precision).reshape(b, s, d.heads, d.head_dim)
    q = _rope(q, positions, d.rope_theta)
    k = _rope(k, positions, d.rope_theta)
    k, v = _round(k, precision), _round(v, precision)  # the K/V cache
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q, precision), k,
                        precision=HIGHEST) * d.head_dim ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision), v, precision=HIGHEST)
    x = x + _mm("bsh,hd->bsd", att.reshape(b, s, -1), w["wo"], precision)
    h = _rms(x, w["norm2"], d.norm_eps)
    g = _mm("bsd,df->bsf", h, w["w_gate"], precision)
    u = _mm("bsd,df->bsf", h, w["w_up"], precision)
    x = x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w["w_down"], precision)
    return x, k, v


def _rel_err(got, want):
    """Frobenius norm of ``got - want`` over that of ``want``."""
    got = got.astype(jnp.float32)
    return jnp.sqrt(jnp.sum((got - want) ** 2)) / jnp.maximum(jnp.sqrt(jnp.sum(want ** 2)), 1e-30)


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _forward(d: Dims, precision: str, w, tokens, first, n_out: int,
             kv_cmp: Optional[Tuple] = None):
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    if kv_cmp is None:
        def body(x, lw):
            x, k, v = _layer(d, precision, x, lw)
            return x, (k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))

        x, kv = jax.lax.scan(body, x, w["layers"])
        errs = None
    else:
        def body(x, xs):
            lw, kc, vc = xs
            x, k, v = _layer(d, precision, x, lw)
            s = k.shape[1]
            return x, jnp.stack([_rel_err(kc[:, :s], k), _rel_err(vc[:, :s], v)])

        x, errs = jax.lax.scan(body, x, (w["layers"], *kv_cmp))
        kv = None
    x = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=1)
    h = _rms(x, w["final_norm"], d.norm_eps)
    logits = _mm("bsd,dv->bsv", h, w["unembed"], precision)
    return logits, kv, errs


def forward(d: Dims, w: Dict, tokens, first: int, n_out: int, precision: str = "f32",
            kv_cmp: Optional[Tuple] = None):
    """Logits ``(B, n_out, V)`` at positions ``first .. first + n_out - 1`` of
    ``tokens`` ``(B, S)``.

    With ``kv_cmp = (k, v)``, arrays ``(L, B, >= S, H, D)``, also returns per
    layer the relative error of their first ``S`` positions against this
    pass's K/V, ``(L, 2)``; without it, this pass's K/V as bf16
    ``(L, B, S, H, D)`` each.
    """
    return _forward(d, precision, w, jnp.asarray(tokens, jnp.int32), first, n_out,
                    None if kv_cmp is None else tuple(jnp.asarray(a) for a in kv_cmp))


@jax.jit
def logit_gaps(ref_logits, chosen):
    """Per position: the reference's best logit minus its logit of the
    chosen token (0 where the chosen token is the reference's best)."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return best - got
