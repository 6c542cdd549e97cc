"""Plain reference of a Mamba-2 + attention hybrid decoder (Granite-4.0-H), in float32.

Independent of the code under test: it imports nothing from ``src/``. It
follows the published architecture as the configuration file states it:

- embeddings times ``embedding_multiplier``; tied output head, logits
  divided by ``logits_scaling``;
- each layer, of the kind ``layer_types`` gives it: ``x += m * mixer(norm(x))``
  then ``x += m * mlp(norm(x))``, ``m`` the ``residual_multiplier``; RMSNorm
  scaled by ``1 + gamma``; a SwiGLU MLP of ``shared_intermediate_size``;
- attention: GQA, no position embedding, scores times
  ``attention_multiplier``, causal softmax;
- Mamba-2 mixer: ``in_proj`` into ``z``, ``xBC`` and ``dt``; a depthwise
  causal conv with bias and SiLU on ``xBC``; then, per head and step, the
  recurrence ``S_t = exp(dt A) S_{t-1} + dt x_t B_t^T``, ``y_t = S_t C_t + D x_t``,
  run one step at a time (not the chunked algorithm of the program);
  ``RMSNorm(y * silu(z))`` over each group's channels; ``out_proj``.

It draws its own weights from the seed, by the recipe the configuration
file names (``init``): the same draws the served program makes.

Two precisions: ``"f32"`` (every matmul at ``HIGHEST``; the reference) and
``"fp8"`` (the control: every matmul operand, the K/V cache and the conv
inputs rounded to float8 e4m3, the SSM state rounded to bfloat16 after every
step, each one precision below what the configuration states).

Layer by layer: a ``lax.scan`` over the repeats of the layer period, so that
the peak is one period's activations beside the weights.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Dims:
    layer_types: Tuple[str, ...]
    d_model: int
    heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    norm_eps: float
    attn_scale: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    m_heads: int
    m_head_dim: int
    m_state: int
    m_groups: int
    m_conv: int
    embed_std: float

    @classmethod
    def from_config(cls, cfg: Dict) -> "Dims":
        return cls(
            layer_types=tuple(cfg["layer_types"]),
            d_model=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            d_ff=int(cfg["shared_intermediate_size"]),
            vocab=int(cfg["vocab_size"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            attn_scale=float(cfg["attention_multiplier"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]),
            m_heads=int(cfg["mamba_n_heads"]),
            m_head_dim=int(cfg["mamba_d_head"]),
            m_state=int(cfg["mamba_d_state"]),
            m_groups=int(cfg["mamba_n_groups"]),
            m_conv=int(cfg["mamba_d_conv"]),
            embed_std=float(cfg["assumed"]["embed_std"]),
        )

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    @property
    def inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_ch(self) -> int:
        return self.inner + 2 * self.m_groups * self.m_state

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern whose repeats make ``layer_types``."""
        n = len(self.layer_types)
        for p in range(1, n + 1):
            if n % p == 0 and all(self.layer_types[i] == self.layer_types[i % p]
                                  for i in range(n)):
                return self.layer_types[:p]
        raise AssertionError("unreachable")


# ------------------------------------------------------------------ weights
def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.bfloat16)


def _attention_weights(d: Dims, key, n: int) -> Dict:
    dm, hd, kvd = d.d_model, d.heads * d.head_dim, d.kv_heads * d.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": _normal(k1, (n, dm, hd), dm ** -0.5),
        "wk": _normal(k2, (n, dm, kvd), dm ** -0.5),
        "wv": _normal(k3, (n, dm, kvd), dm ** -0.5),
        "wo": _normal(k4, (n, hd, dm), hd ** -0.5),
    }


def _mamba_weights(d: Dims, key, n: int) -> Dict:
    dm, inner, heads = d.d_model, d.inner, d.m_heads
    k_in, k_cw, k_cb, k_a, k_dt, k_out = jax.random.split(key, 6)
    a = jax.random.uniform(k_a, (n, heads), jnp.float32, 1.0, 16.0)
    u = jax.random.uniform(k_dt, (n, heads), jnp.float32)
    step = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    step = jnp.maximum(step, 1e-4)
    return {
        "in_proj": _normal(k_in, (n, dm, inner + d.conv_ch + heads), dm ** -0.5),
        "conv_w": _normal(k_cw, (n, d.m_conv, d.conv_ch), d.m_conv ** -0.5),
        "conv_b": _normal(k_cb, (n, d.conv_ch), d.m_conv ** -0.5),
        "A_log": jnp.log(a),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "D": jnp.ones((n, heads), jnp.float32),
        "norm": jnp.zeros((n, inner), jnp.bfloat16),
        "out_proj": _normal(k_out, (n, inner, dm), inner ** -0.5),
    }


def init_weights(d: Dims, seed: int) -> Dict:
    """Weights from the seed, as the configuration's ``init`` recipe draws
    them: key split into (embed, -, -, layers); the layer key into one key
    per position of the layer period; each of those into (mix, ffn, norm),
    arrays stacked over the period's repeats; attention mix into (wq, wk, wv,
    wo); Mamba mix into (in_proj, conv_w, conv_b, A, dt, out_proj); ffn into
    (gate, up, down). Normal draws in float32 times the scale (the
    embedding's: ``embed_std``), rounded to bfloat16; norms 0; ``A``
    uniform in [1, 16] and ``dt`` log-uniform in [1e-3, 1e-1] (at least
    1e-4), kept as ``log A`` and ``dt``'s inverse softplus in float32; ``D`` 1.

    Drawn one operation at a time, not in one jitted program, as the program
    draws them (a fused program may round some weights differently)."""
    period = d.period
    n = len(d.layer_types) // len(period)
    dm, ff = d.d_model, d.d_ff
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    pos_keys = jax.random.split(keys[3], len(period))
    layers = []
    for kind, key in zip(period, pos_keys):
        k_mix, k_ffn, _ = jax.random.split(key, 3)
        f1, f2, f3 = jax.random.split(k_ffn, 3)
        lw = {"norm1": jnp.zeros((n, dm), jnp.bfloat16),
              "norm2": jnp.zeros((n, dm), jnp.bfloat16),
              "w_gate": _normal(f1, (n, dm, ff), dm ** -0.5),
              "w_up": _normal(f2, (n, dm, ff), dm ** -0.5),
              "w_down": _normal(f3, (n, ff, dm), ff ** -0.5)}
        lw.update(_attention_weights(d, k_mix, n) if kind == "attention"
                  else _mamba_weights(d, k_mix, n))
        layers.append(lw)
    return {
        "embed": _normal(keys[0], (d.vocab, dm), d.embed_std),
        "final_norm": jnp.zeros((dm,), jnp.bfloat16),
        "layers": layers,
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


def _attention(d: Dims, precision: str, h, w):
    b, s, _ = h.shape
    q = _mm("bsd,dh->bsh", h, w["wq"], precision).reshape(b, s, d.heads, d.head_dim)
    k = _mm("bsd,dh->bsh", h, w["wk"], precision).reshape(b, s, d.kv_heads, d.head_dim)
    v = _mm("bsd,dh->bsh", h, w["wv"], precision).reshape(b, s, d.kv_heads, d.head_dim)
    k, v = _round(k, precision), _round(v, precision)  # the K/V cache
    rep = d.heads // d.kv_heads
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q, precision), jnp.repeat(k, rep, axis=2),
                        precision=HIGHEST) * d.attn_scale
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, precision), jnp.repeat(v, rep, axis=2),
                     precision=HIGHEST)
    return _mm("bsh,hd->bsd", att.reshape(b, s, -1), w["wo"], precision), {"k": k, "v": v}


def _mamba(d: Dims, precision: str, h, w):
    b, s, _ = h.shape
    inner, heads, p, n, g = d.inner, d.m_heads, d.m_head_dim, d.m_state, d.m_groups
    proj = _mm("bsd,de->bse", h, w["in_proj"], precision)
    z, xbc, dt_raw = proj[..., :inner], proj[..., inner:inner + d.conv_ch], \
        proj[..., inner + d.conv_ch:]
    xbc = _round(xbc, precision)  # the conv window caches these
    k = d.m_conv
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    conv = sum(padded[:, i:i + s] * w["conv_w"][i].astype(jnp.float32) for i in range(k))
    xbc_c = jax.nn.silu(conv + w["conv_b"].astype(jnp.float32))
    x = xbc_c[..., :inner].reshape(b, s, heads, p)
    bm = jnp.repeat(xbc_c[..., inner:inner + g * n].reshape(b, s, g, n), heads // g, axis=2)
    cm = jnp.repeat(xbc_c[..., inner + g * n:].reshape(b, s, g, n), heads // g, axis=2)
    dt = jax.nn.softplus(dt_raw + w["dt_bias"])               # (B, S, H)
    a = -jnp.exp(w["A_log"])                                  # (H,)

    def step(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        if precision == "fp8":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HIGHEST)

    time_major = [jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, dt)]
    state, y = jax.lax.scan(step, jnp.zeros((b, heads, p, n), jnp.float32), time_major)
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x
    gated = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(b, s, g, inner // g)
    normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + d.norm_eps)
    normed = normed.reshape(b, s, inner) * (1.0 + w["norm"].astype(jnp.float32))
    out = _mm("bse,ed->bsd", normed, w["out_proj"], precision)
    return out, {"ssm": state, "conv": padded[:, -(k - 1):]}


def _mlp(d: Dims, precision: str, h, w):
    gate = _mm("bsd,df->bsf", h, w["w_gate"], precision)
    up = _mm("bsd,df->bsf", h, w["w_up"], precision)
    return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["w_down"], precision)


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _forward(d: Dims, precision: str, w, tokens, first, n_out: int):
    m = d.residual_multiplier
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32) * d.embedding_multiplier

    def period(x, layers):
        caches = []
        for kind, lw in zip(d.period, layers):
            h = _rms(x, lw["norm1"], d.norm_eps)
            mix = _attention if kind == "attention" else _mamba
            y, cache = mix(d, precision, h, lw)
            x = x + m * y
            x = x + m * _mlp(d, precision, _rms(x, lw["norm2"], d.norm_eps), lw)
            caches.append(cache)
        return x, caches

    x, caches = jax.lax.scan(period, x, w["layers"])
    x = jax.lax.dynamic_slice_in_dim(x, first, n_out, axis=1)
    h = _rms(x, w["final_norm"], d.norm_eps)
    logits = _mm("bsd,vd->bsv", h, w["embed"], precision) / d.logits_scaling
    return logits, caches


def forward(d: Dims, w: Dict, tokens, first: int, n_out: int, precision: str = "f32"):
    """Logits ``(B, n_out, V)`` at positions ``first .. first + n_out - 1`` of
    ``tokens`` ``(B, S)``, and the decode cache after the last position: per
    position of the layer period, stacked over its repeats, attention's
    ``k``/``v`` ``(R, B, S, Hkv, D)`` or Mamba's ``ssm`` ``(R, B, H, P, N)``
    and ``conv`` ``(R, B, d_conv - 1, C)`` (the last conv inputs), float32."""
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
