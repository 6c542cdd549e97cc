"""Plain reference of a decoder with multi-head latent attention and a share
of routed experts (DeepSeek-V2-Lite), in float32.

Independent of the code under test: it imports nothing from ``src/``. It
follows the published architecture as the configuration file states it:

- untied embedding and output head; RMSNorm scaled by ``1 + gamma``;
- each layer ``x += attn(norm(x))``, then ``x += ffn(norm(x))``;
- attention (MLA, expanded at every position): ``q = x Wq`` per head
  ``[nope | rope]``; ``[c | k_pe] = x Wkv_a``, the latent ``c`` RMS-normed;
  per head ``[k_nope | v] = c Wkv_b``; keys ``[k_nope | k_pe]`` (one rotary
  key shared by all heads); rotary on adjacent pairs at YaRN's frequencies
  (``rope_scaling``); scores times ``qk_head_dim ** -0.5 * mscale ** 2``,
  ``mscale = 0.1 * mscale_all_dim * ln(factor) + 1`` (DeepSeek-V2's own
  softmax scale); causal softmax; ``out Wo``. Its decode cache is the
  normed latent ``c_kv`` and the rotated ``k_pe`` of every position;
- ffn: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; the rest a softmax router over all the published
  routed experts (float32), greedy top ``num_experts_per_tok``, the weights
  renormalised only where ``norm_topk_prob`` says so, times
  ``routed_scaling_factor``; the part of the layer that the experts held
  here give (``n_routed_experts`` of them, from rank ``ep_rank`` of the
  ``expert_parallel`` layout), each a SwiGLU of ``moe_intermediate_size``
  computed on every token and weighted by its routing weight (0 where the
  token did not choose it); plus the shared experts, one SwiGLU of
  ``n_shared_experts * moe_intermediate_size``.

It draws its own weights from the seed, by the recipe the configuration
file names (``init``): the same draws the served program makes.

Two precisions: ``"f32"`` (every matmul at ``HIGHEST``; the reference) and
``"fp8"`` (the control: every matmul operand and the latent cache rounded to
float8 e4m3, one precision below the bf16 the configuration states).

Layer by layer: a ``lax.scan`` over each group's stacked layers (the dense
layer, then the expert layers), and attention one batch row at a time, so
that the peak is one layer's activations beside the weights.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    d_ff: int
    vocab: int
    norm_eps: float
    rank: int
    nope: int
    rope: int
    v_dim: int
    theta: float
    yarn: Tuple[Tuple[str, float], ...]
    experts: int          # routed experts the router chooses among
    held: int             # of them, held here
    first_held: int
    top_k: int
    d_expert: int
    shared: int
    norm_topk: bool
    routed_scale: float
    embed_std: float

    @classmethod
    def from_config(cls, cfg: Dict) -> "Dims":
        held = int(cfg["n_routed_experts"])
        return cls(
            layers=int(cfg["num_hidden_layers"]),
            dense_layers=int(cfg["first_k_dense_replace"]),
            d_model=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            d_ff=int(cfg["intermediate_size"]),
            vocab=int(cfg["vocab_size"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            rank=int(cfg["kv_lora_rank"]),
            nope=int(cfg["qk_nope_head_dim"]),
            rope=int(cfg["qk_rope_head_dim"]),
            v_dim=int(cfg["v_head_dim"]),
            theta=float(cfg["rope_theta"]),
            yarn=tuple(sorted((k, v) for k, v in cfg["rope_scaling"].items()
                              if k != "type")),
            experts=int(cfg["published"]["n_routed_experts"]),
            held=held,
            first_held=int(cfg["expert_parallel"]["ep_rank"]) * held,
            top_k=int(cfg["num_experts_per_tok"]),
            d_expert=int(cfg["moe_intermediate_size"]),
            shared=int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
            norm_topk=bool(cfg["norm_topk_prob"]),
            routed_scale=float(cfg["routed_scaling_factor"]),
            embed_std=float(cfg["assumed"]["embed_std"]),
        )

    @property
    def qk_dim(self) -> int:
        return self.nope + self.rope


def softmax_scale(d: Dims) -> float:
    """``qk_head_dim ** -0.5``, times YaRN's mscale squared (DeepSeek-V2's
    modeling code; the published ``mscale_all_dim`` is nonzero)."""
    y = dict(d.yarn)
    m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
    return d.qk_dim ** -0.5 * m * m


def inv_freq(d: Dims) -> np.ndarray:
    """YaRN's inverse frequencies of the ``rope``-wide rotary pairs: the
    correction range from ``beta_fast`` and ``beta_slow`` rotations over the
    original context (floored, ceiled), a linear ramp between the original
    frequencies and those divided by ``factor``."""
    y, dim, base = dict(d.yarn), d.rope, d.theta

    def correction_dim(rotations):
        return dim * math.log(y["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (y["factor"] * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return (interpolation * (1 - keep) + extrapolation * keep).astype(np.float32)


# ------------------------------------------------------------------ weights
def _normal(key, shape, scale, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _swiglu_weights(key, n: int, dm: int, ff: int) -> Dict:
    g, u, dn = jax.random.split(key, 3)
    return {"w_gate": _normal(g, (n, dm, ff), dm ** -0.5),
            "w_up": _normal(u, (n, dm, ff), dm ** -0.5),
            "w_down": _normal(dn, (n, ff, dm), ff ** -0.5)}


def _expert_weights(d: Dims, key, n: int) -> Dict:
    """Router, the held experts (expert ``e`` from ``fold_in(key_w, e)``,
    stacked on axis 1) and the shared experts."""
    dm, ff = d.d_model, d.d_expert
    k_router, k_gate, k_up, k_down, k_shared = jax.random.split(key, 5)
    held = range(d.first_held, d.first_held + d.held)

    def experts(k, shape, scale):
        return jnp.stack([_normal(jax.random.fold_in(k, e), (n, *shape), scale) for e in held],
                         axis=1)

    return {"router": _normal(k_router, (n, dm, d.experts), dm ** -0.5, jnp.float32),
            "e_gate": experts(k_gate, (dm, ff), dm ** -0.5),
            "e_up": experts(k_up, (dm, ff), dm ** -0.5),
            "e_down": experts(k_down, (ff, dm), ff ** -0.5),
            **{"s_" + k[2:]: v for k, v in _swiglu_weights(k_shared, n, dm, d.shared).items()}}


def init_weights(d: Dims, seed: int) -> Dict:
    """Weights from the seed, as the configuration's ``init`` recipe draws
    them: key split into (embed, unembed, -, dense group, expert group);
    each group key split once (one position) and that into (mix, ffn,
    norm), arrays stacked over the group's layers; mix into (wq, wkv_a,
    wkv_b, wo); a dense ffn into (gate, up, down); an expert ffn into
    (router, gate, up, down, shared), the expert weights per expert as
    above, the shared experts' key into (gate, up, down). Normal draws in
    float32 times the scale, rounded to bfloat16 (the router kept in
    float32); norms 0.

    Drawn one operation at a time, as the program draws them."""
    dm, h = d.d_model, d.heads
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    groups = []
    for gi, n in enumerate((d.dense_layers, d.layers - d.dense_layers)):
        (pos_key,) = jax.random.split(keys[3 + gi], 1)
        k_mix, k_ffn, _ = jax.random.split(pos_key, 3)
        k1, k2, k3, k4 = jax.random.split(k_mix, 4)
        lw = {"norm1": jnp.zeros((n, dm), jnp.bfloat16),
              "norm2": jnp.zeros((n, dm), jnp.bfloat16),
              "wq": _normal(k1, (n, dm, h * d.qk_dim), dm ** -0.5),
              "wkv_a": _normal(k2, (n, dm, d.rank + d.rope), dm ** -0.5),
              "kv_norm": jnp.zeros((n, d.rank), jnp.bfloat16),
              "wkv_b": _normal(k3, (n, d.rank, h * (d.nope + d.v_dim)), d.rank ** -0.5),
              "wo": _normal(k4, (n, h * d.v_dim, dm), (h * d.v_dim) ** -0.5)}
        lw.update(_swiglu_weights(k_ffn, n, dm, d.d_ff) if gi == 0 else
                  _expert_weights(d, k_ffn, n))
        groups.append(lw)
    return {
        "embed": _normal(keys[0], (d.vocab, dm), d.embed_std),
        "unembed": _normal(keys[1], (dm, d.vocab), dm ** -0.5),
        "final_norm": jnp.zeros((dm,), jnp.bfloat16),
        "groups": groups,
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


def _rotate(x, cos, sin):
    """Rotary on adjacent pairs ``(x[2i], x[2i+1])``; x: (B, S, H, D),
    cos/sin: (S, D/2)."""
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape)


def _attention(d: Dims, precision: str, h, w):
    b, s, _ = h.shape
    q = _mm("bsd,dh->bsh", h, w["wq"], precision).reshape(b, s, d.heads, d.qk_dim)
    kv = _mm("bsd,dr->bsr", h, w["wkv_a"], precision)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq(d))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    c_kv = _round(_rms(kv[..., :d.rank], w["kv_norm"], d.norm_eps), precision)  # the cache
    k_pe = _round(_rotate(kv[..., None, d.rank:], cos, sin)[:, :, 0], precision)
    q = jnp.concatenate([q[..., :d.nope], _rotate(q[..., d.nope:], cos, sin)], -1)
    kvb = _mm("bsr,rh->bsh", c_kv, w["wkv_b"], precision).reshape(b, s, d.heads, -1)
    k = jnp.concatenate([kvb[..., :d.nope],
                         jnp.broadcast_to(k_pe[:, :, None], (b, s, d.heads, d.rope))], -1)
    v = kvb[..., d.nope:]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def one_row(args):
        qr, kr, vr = args
        scores = jnp.einsum("qhd,khd->hqk", _round(qr, precision), _round(kr, precision),
                            precision=HIGHEST) * softmax_scale(d)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round(probs, precision), _round(vr, precision),
                          precision=HIGHEST)

    att = jax.lax.map(one_row, (q, k, v))
    out = _mm("bsh,hd->bsd", att.reshape(b, s, -1), w["wo"], precision)
    return out, {"c_kv": c_kv, "k_pe": k_pe}


def _swiglu(precision, h, gate, up, down):
    return _mm("bsf,fd->bsd", jax.nn.silu(_mm("bsd,df->bsf", h, gate, precision))
               * _mm("bsd,df->bsf", h, up, precision), down, precision)


def route(d: Dims, h, router):
    """Per token and held expert, the routing weight (0 where the token's
    top ``top_k`` of all ``experts`` leave it out): (B, S, held)."""
    probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", h, router.astype(jnp.float32),
                                      precision=HIGHEST), axis=-1)
    top, idx = jax.lax.top_k(probs, d.top_k)
    if d.norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * d.routed_scale
    held = d.first_held + jnp.arange(d.held)
    return jnp.sum(jnp.where(idx[..., None] == held, top[..., None], 0.0), axis=-2)


def _experts(d: Dims, precision: str, h, w):
    weight = route(d, h, w["router"])
    y = _swiglu(precision, h, w["s_gate"], w["s_up"], w["s_down"])
    for e in range(d.held):
        y = y + weight[..., e:e + 1] * _swiglu(precision, h, w["e_gate"][e], w["e_up"][e],
                                               w["e_down"][e])
    return y


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _forward(d: Dims, precision: str, w, tokens, first, n_out: int):
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    caches = []
    for gi, gw in enumerate(w["groups"]):
        def layer(x, lw, gi=gi):
            y, cache = _attention(d, precision, _rms(x, lw["norm1"], d.norm_eps), lw)
            x = x + y
            h = _rms(x, lw["norm2"], d.norm_eps)
            ffn = (_swiglu(precision, h, lw["w_gate"], lw["w_up"], lw["w_down"]) if gi == 0
                   else _experts(d, precision, h, lw))
            return x + ffn, cache

        x, cache = jax.lax.scan(layer, x, gw)
        caches.append(cache)
    x = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=1)
    h = _rms(x, w["final_norm"], d.norm_eps)
    return _mm("bsd,dv->bsv", h, w["unembed"], precision), caches


def forward(d: Dims, w: Dict, tokens, first: int, n_out: int, precision: str = "f32"):
    """Logits ``(B, n_out, V)`` at positions ``first .. first + n_out - 1`` of
    ``tokens`` ``(B, S)``, and the decode cache of every position: per group
    (the dense layers, then the expert layers), ``c_kv`` ``(R, B, S, rank)``
    and ``k_pe`` ``(R, B, S, rope)``, float32."""
    return _forward(d, precision, w, jnp.asarray(tokens, jnp.int32), first, n_out)


def rel_err(got, want):
    """Per entry of the leading axis (a layer), the Frobenius norm of
    ``got - want`` over that of ``want``."""
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    axes = tuple(range(1, want.ndim))
    num = jnp.sqrt(jnp.sum((got - want) ** 2, axis=axes))
    return num / jnp.maximum(jnp.sqrt(jnp.sum(want ** 2, axis=axes)), 1e-30)


@jax.jit
def logit_gaps(ref_logits, chosen):
    """Per position: the reference's best logit minus its logit of the
    chosen token (0 where the chosen token is the reference's best)."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return best - got
