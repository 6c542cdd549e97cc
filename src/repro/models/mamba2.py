"""Mamba-2 mixer (Dao & Gu, arXiv:2405.21060), as Granite-4.0-H uses it.

Per head ``h`` of ``head_dim`` channels and step ``t``:

    dt  = softplus(dt_raw + dt_bias)          (per head)
    A   = -exp(A_log)                         (per head)
    S_t = exp(dt * A) * S_{t-1} + dt * (x_t ⊗ B_t)      S: (head_dim, d_state)
    y_t = S_t · C_t + D * x_t

``in_proj`` splits into the gate ``z``, ``xBC`` and ``dt_raw``; ``xBC`` goes
through a depthwise causal conv (width ``d_conv``, with bias) and SiLU, then
splits into ``x``, ``B`` and ``C`` (``n_groups`` groups of ``d_state``,
shared by the group's heads). The output is RMS-normalised after the gate,
``norm(y * silu(z))`` over the inner channels of a group, then ``out_proj``.

The full-sequence pass is the chunked SSD ("state space duality"): within a
chunk the recurrence is a masked, decay-weighted attention; across chunks a
recurrence over per-chunk states. No ``(B, S, H, head_dim, d_state)`` tensor
is built: the states exist once per chunk. Decode runs the recurrence one
step at a time on the cached state, in float32, beside a window of the last
``d_conv - 1`` conv inputs.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dtype_of, normal_init


def dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    """(inner channels, heads, conv channels, B/C width)."""
    m = cfg.mamba
    inner = m.expand * cfg.d_model
    bc = m.n_groups * m.d_state
    return inner, inner // m.head_dim, inner + 2 * bc, bc


def mamba_params(cfg: ModelConfig, key, n: int) -> Dict:
    """Stacked mixer parameters for ``n`` layers. ``A_log`` and ``dt_bias``
    are drawn as Mamba-2 draws them: ``A`` uniform in [1, 16]; ``dt``
    log-uniform in [1e-3, 1e-1] (at least 1e-4), stored as its inverse
    softplus. ``D`` is 1 and the gated norm's gamma 0 (scale 1). ``A_log``,
    ``dt_bias`` and ``D`` are float32, the rest the model's dtype."""
    m, d, dt = cfg.mamba, cfg.d_model, dtype_of(cfg)
    inner, heads, conv_ch, _ = dims(cfg)
    k_in, k_cw, k_cb, k_a, k_dt, k_out = jax.random.split(key, 6)
    a = jax.random.uniform(k_a, (n, heads), jnp.float32, 1.0, 16.0)
    u = jax.random.uniform(k_dt, (n, heads), jnp.float32)
    step = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    step = jnp.maximum(step, 1e-4)
    return {
        "in_proj": normal_init(k_in, (n, d, inner + conv_ch + heads), d ** -0.5, dt),
        "conv_w": normal_init(k_cw, (n, m.d_conv, conv_ch), m.d_conv ** -0.5, dt),
        "conv_b": normal_init(k_cb, (n, conv_ch), m.d_conv ** -0.5, dt),
        "A_log": jnp.log(a),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "D": jnp.ones((n, heads), jnp.float32),
        "norm": jnp.zeros((n, inner), dt),
        "out_proj": normal_init(k_out, (n, inner, d), inner ** -0.5, dt),
    }


def mamba_specs() -> Dict:
    return {
        "in_proj": (None, "fsdp", None),
        "conv_w": (None, None, None),
        "conv_b": (None, None),
        "A_log": (None, None),
        "dt_bias": (None, None),
        "D": (None, None),
        "norm": (None, None),
        "out_proj": (None, None, "fsdp"),
    }


def mamba_init_state(cfg: ModelConfig, n: int, batch: int) -> Dict:
    """Decode state of ``n`` layers: the SSM state in float32 and the last
    ``d_conv - 1`` conv inputs in the model's dtype."""
    m = cfg.mamba
    _, heads, conv_ch, _ = dims(cfg)
    return {
        "ssm": jnp.zeros((n, batch, heads, m.head_dim, m.d_state), jnp.float32),
        "conv": jnp.zeros((n, batch, m.d_conv - 1, conv_ch), dtype_of(cfg)),
    }


def mamba_state_specs() -> Dict:
    return {"ssm": (None, "batch", None, None, None), "conv": (None, "batch", None, None)}


# ------------------------------------------------------------------ pieces
def _split_proj(cfg: ModelConfig, zxbcdt: jax.Array):
    inner, _, conv_ch, _ = dims(cfg)
    return (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv_ch],
            zxbcdt[..., inner + conv_ch:])


def _split_xbc(cfg: ModelConfig, xbc: jax.Array):
    inner, heads, _, bc = dims(cfg)
    m = cfg.mamba
    x = xbc[..., :inner].reshape(*xbc.shape[:-1], heads, m.head_dim)
    b = xbc[..., inner:inner + bc].reshape(*xbc.shape[:-1], m.n_groups, m.d_state)
    c = xbc[..., inner + bc:].reshape(*xbc.shape[:-1], m.n_groups, m.d_state)
    return x, b, c


def _gated_norm(cfg: ModelConfig, y: jax.Array, z: jax.Array, gamma: jax.Array) -> jax.Array:
    """RMSNorm of ``y * silu(z)`` over each group's inner channels, scaled by
    ``1 + gamma``; float32 in, the model's dtype out."""
    g = cfg.mamba.n_groups
    h = y * jax.nn.silu(z.astype(jnp.float32))
    hg = h.reshape(*h.shape[:-1], g, h.shape[-1] // g)
    var = jnp.mean(hg * hg, axis=-1, keepdims=True)
    h = (hg * jax.lax.rsqrt(var + cfg.norm_eps)).reshape(h.shape)
    return (h * (1.0 + gamma.astype(jnp.float32))).astype(dtype_of(cfg))


def _dt_a(p: Dict, dt_raw: jax.Array):
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    return dt, -jnp.exp(p["A_log"])


def _segsum_exp(a_cum: jax.Array) -> jax.Array:
    """exp(a_cum[i] - a_cum[j]) for j <= i, else 0, over the last axis:
    the decay from step j to step i."""
    t = a_cum.shape[-1]
    diff = a_cum[..., :, None] - a_cum[..., None, :]
    causal = jnp.tril(jnp.ones((t, t), bool))
    return jnp.exp(jnp.where(causal, diff, -jnp.inf))


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """The SSD of a sequence from a zero state, chunk by chunk.

    x: (B, S, H, P); dt: (B, S, H); a: (H,) (negative); b, c: (B, S, G, N),
    each group shared by ``H / G`` consecutive heads; all float32. Returns y
    (B, S, H, P) without the ``D`` term, and the state after the last step,
    (B, H, P, N). ``S`` need not be a multiple of ``chunk``: the tail is
    padded with ``dt = 0``, which leaves the state as it is.
    """
    bsz, s, h, p = x.shape
    g = b.shape[2]
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk

    def chunks(t):  # (B, S, ...) -> (B, C, L, ...)
        return t.reshape(bsz, nc, chunk, *t.shape[2:])

    # heads as (group, head in group): j
    xdt = chunks((x * dt[..., None]).reshape(bsz, -1, g, h // g, p))  # (B, C, L, G, J, P)
    b, c = chunks(b), chunks(c)                                       # (B, C, L, G, N)
    la = chunks((dt * a).reshape(bsz, -1, g, h // g))                 # (B, C, L, G, J)
    a_cum = jnp.cumsum(jnp.transpose(la, (0, 3, 4, 1, 2)), axis=-1)   # (B, G, J, C, L)
    # within a chunk: y_i = sum_{j <= i} (C_i . B_j) decay(j -> i) dt_j x_j
    scores = jnp.einsum("bclgn,bcsgn->bgcls", c, b)[:, :, None] * _segsum_exp(a_cum)
    y = jnp.einsum("bgjcls,bcsgjp->bclgjp", scores, xdt)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(a_cum[..., -1:] - a_cum)                         # (B, G, J, C, L)
    weighted = xdt * jnp.transpose(to_end, (0, 3, 4, 1, 2))[..., None]
    states = jnp.einsum("bclgn,bclgjp->bcgjpn", b, weighted)
    # across chunks: the state entering each chunk, and the last one's exit
    ends = jnp.pad(a_cum[..., -1], [(0, 0)] * 3 + [(1, 0)])          # (B, G, J, C + 1)
    carry = _segsum_exp(jnp.cumsum(ends, axis=-1))                    # (..., C + 1, C + 1)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    states = jnp.einsum("bgjzc,bcgjpn->bzgjpn", carry, states)
    entering, final = states[:, :-1], states[:, -1]
    from_start = jnp.transpose(jnp.exp(a_cum), (0, 3, 4, 1, 2))[..., None]
    y = y + jnp.einsum("bclgn,bcgjpn->bclgjp", c, entering) * from_start
    return y.reshape(bsz, nc * chunk, h, p)[:, :s], final.reshape(bsz, h, p, -1)


def _conv_full(xbc: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """Depthwise causal conv over the sequence, zero history; then SiLU.
    xbc: (B, S, C); w: (K, C)."""
    k = w.shape[0]
    s = xbc.shape[1]
    xp = jnp.pad(xbc.astype(jnp.float32), [(0, 0), (k - 1, 0), (0, 0)])
    return _conv_out([xp[:, i:i + s] for i in range(k)], w, bias).astype(xbc.dtype)


def _conv_out(taps, w: jax.Array, bias: jax.Array) -> jax.Array:
    """SiLU of the conv's sum over its taps (oldest first), in float32."""
    out = sum(t * w[i].astype(jnp.float32) for i, t in enumerate(taps))
    return jax.nn.silu(out + bias.astype(jnp.float32))


# ------------------------------------------------------------------ passes
def mamba_full(p: Dict, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """Full-sequence mixer. x: (B, S, d). Returns the output (B, S, d) and
    the decode state after the last position (``ssm``, ``conv``)."""
    m = cfg.mamba
    z, xbc, dt_raw = _split_proj(cfg, jnp.einsum("bsd,de->bse", x, p["in_proj"]))
    xs, b, c = _split_xbc(cfg, _conv_full(xbc, p["conv_w"], p["conv_b"]))
    dt, a = _dt_a(p, dt_raw)
    xs = xs.astype(jnp.float32)
    y, ssm = ssd_chunked(xs, dt, a, b.astype(jnp.float32), c.astype(jnp.float32), m.chunk)
    y = y + p["D"][:, None] * xs
    y = _gated_norm(cfg, y.reshape(*y.shape[:2], -1), z, p["norm"])
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    window = jnp.pad(xbc, [(0, 0), (m.d_conv - 1, 0), (0, 0)])[:, -(m.d_conv - 1):]
    return out, {"ssm": ssm, "conv": window}


def mamba_decode_step(
    p: Dict, x: jax.Array, ssm: jax.Array, conv: jax.Array, cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token. x: (B, 1, d); ssm: (B, H, P, N) float32; conv:
    (B, d_conv - 1, C). Returns (y (B, 1, d), ssm, conv)."""
    z, xbc, dt_raw = _split_proj(cfg, jnp.einsum("bsd,de->bse", x, p["in_proj"])[:, 0])
    window = jnp.concatenate([conv, xbc[:, None].astype(conv.dtype)], axis=1)
    wf = window.astype(jnp.float32)
    xc = _conv_out([wf[:, i] for i in range(wf.shape[1])], p["conv_w"], p["conv_b"])
    xs, b, c = _split_xbc(cfg, xc.astype(xbc.dtype))
    dt, a = _dt_a(p, dt_raw)                                 # (B, H), (H,)
    xs = xs.astype(jnp.float32)
    per_group = xs.shape[1] // b.shape[1]                    # heads of a B/C group
    b = jnp.repeat(b.astype(jnp.float32), per_group, axis=1)  # (B, H, N)
    c = jnp.repeat(c.astype(jnp.float32), per_group, axis=1)
    ssm = (jnp.exp(dt * a)[..., None, None] * ssm
           + (dt[..., None] * xs)[..., None] * b[:, :, None, :])
    y = jnp.sum(ssm * c[:, :, None, :], axis=-1) + p["D"][:, None] * xs
    y = _gated_norm(cfg, y.reshape(y.shape[0], -1), z, p["norm"])
    out = jnp.einsum("be,ed->bd", y, p["out_proj"])[:, None]
    return out, ssm, window[:, 1:]
