"""LM assembly for the architecture zoo: embed -> layer-group scans -> logits.

Layer stacks compile as one ``lax.scan`` per *group* (a repeated pattern of
layer kinds) with rematerialization, so the HLO stays one-layer-sized even
for 96-layer models and the dry-run compiles quickly.  Per layer kind:

  attn  — GQA attention (optionally local-window) + gated MLP (or MoE)
  rec   — RG-LRU recurrence + gated MLP
  rwkv  — RWKV-6 time-mix + gated MLP (channel-mix swapped for SwiGLU of the
          same width; parameter-count equivalent — noted in DESIGN.md)
  mamba — Mamba-2 mixer (chunked SSD) + gated MLP

Granite's scalars (``embedding_multiplier``, ``residual_multiplier``,
``logits_scaling``) apply where the configuration sets them; at 1 they add
no operation.

Entry points: ``init_params`` / ``param_specs`` / ``forward`` /
``loss_and_aux`` / ``prefill`` / ``init_cache`` / ``cache_specs`` /
``decode_step`` / ``rewritten_leaves``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import with_logical
from .attention import (
    attention_decode,
    attention_full,
    attn_params,
    attn_specs,
    init_kv_cache,
    kv_cache_specs,
)
from .config import ModelConfig
from .layers import dtype_of, mlp_apply, mlp_params, mlp_specs, normal_init, rms_norm
from .mamba2 import (
    mamba_decode_step,
    mamba_full,
    mamba_init_state,
    mamba_params,
    mamba_specs,
    mamba_state_specs,
)
from .moe import moe_apply, moe_params, moe_specs
from .rglru import (
    rglru_decode_step,
    rglru_full,
    rglru_init_state,
    rglru_params,
    rglru_specs,
    rglru_state_specs,
)
from .rwkv6 import (
    rwkv_decode_step,
    rwkv_init_state,
    rwkv_params,
    rwkv_scan_full,
    rwkv_specs,
    rwkv_state_specs,
)

Params = Dict[str, Any]


def _layer_uses_moe(cfg: ModelConfig, kind: str) -> bool:
    return cfg.moe is not None and kind == "attn"


# ------------------------------------------------------------------- params
def _sublayer_params(cfg: ModelConfig, kind: str, key, n: int) -> Dict:
    k_mix, k_ffn, k_norm = jax.random.split(key, 3)
    dt = dtype_of(cfg)
    p: Dict[str, Any] = {
        "norm1": jnp.zeros((n, cfg.d_model), dt),
        "norm2": jnp.zeros((n, cfg.d_model), dt),
    }
    if kind == "attn":
        p["attn"] = attn_params(cfg, k_mix, n)
    elif kind == "rec":
        p["rec"] = rglru_params(cfg, k_mix, n)
    elif kind == "rwkv":
        p["rwkv"] = rwkv_params(cfg, k_mix, n)
    elif kind == "mamba":
        p["mamba"] = mamba_params(cfg, k_mix, n)
    else:
        raise ValueError(kind)
    if _layer_uses_moe(cfg, kind):
        p["moe"] = moe_params(cfg, k_ffn, n)
    else:
        p["mlp"] = mlp_params(cfg, k_ffn, n)
    return p


def _sublayer_specs(cfg: ModelConfig, kind: str, tp: int) -> Dict:
    p: Dict[str, Any] = {"norm1": (None, None), "norm2": (None, None)}
    if kind == "attn":
        p["attn"] = attn_specs(cfg, tp)
    elif kind == "rec":
        p["rec"] = rglru_specs()
    elif kind == "rwkv":
        p["rwkv"] = rwkv_specs()
    elif kind == "mamba":
        p["mamba"] = mamba_specs()
    if _layer_uses_moe(cfg, kind):
        p["moe"] = moe_specs(cfg)
    else:
        p["mlp"] = mlp_specs()
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    dt = dtype_of(cfg)
    keys = jax.random.split(key, 3 + len(cfg.groups))
    params: Params = {
        "embed": normal_init(keys[0], (cfg.vocab, cfg.d_model), cfg.embed_std, dt),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = normal_init(
            keys[1], (cfg.d_model, cfg.vocab), cfg.d_model ** -0.5, dt
        )
    for gi, (pattern, rep) in enumerate(cfg.groups):
        gkeys = jax.random.split(keys[3 + gi], len(pattern))
        params[f"group{gi}"] = {
            f"pos{pi}": _sublayer_params(cfg, kind, gkeys[pi], rep)
            for pi, kind in enumerate(pattern)
        }
    return params


def param_specs(cfg: ModelConfig, tp: int = 16) -> Params:
    specs: Params = {
        "embed": ("vocab", "fsdp"),
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ("fsdp", "vocab")
    for gi, (pattern, rep) in enumerate(cfg.groups):
        specs[f"group{gi}"] = {
            f"pos{pi}": _sublayer_specs(cfg, kind, tp)
            for pi, kind in enumerate(pattern)
        }
    return specs


# ------------------------------------------------------------------ forward
def _residual(cfg: ModelConfig, x: jax.Array, y: jax.Array) -> jax.Array:
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def _apply_sublayer(
    cfg: ModelConfig, kind: str, lp: Dict, x: jax.Array, positions: jax.Array,
    impl: str,
) -> Tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    if kind == "attn":
        h = attention_full(lp["attn"], h, cfg, positions, window=cfg.attn_window, impl=impl)
    elif kind == "rec":
        h = rglru_full(lp["rec"], h, cfg, impl=impl)
    elif kind == "rwkv":
        h = rwkv_scan_full(lp["rwkv"], h, cfg, impl=impl)
    elif kind == "mamba":
        h, _ = mamba_full(lp["mamba"], h, cfg)
    x = _residual(cfg, x, h)
    h = rms_norm(x, lp["norm2"], cfg.norm_eps)
    if "moe" in lp:
        h, aux = moe_apply(lp["moe"], h, cfg)
    else:
        h = mlp_apply(lp["mlp"], h, cfg)
    return _residual(cfg, x, h), aux


def _run_groups(
    cfg: ModelConfig, params: Params, x: jax.Array, positions: jax.Array, impl: str,
) -> Tuple[jax.Array, jax.Array]:
    aux_total = jnp.zeros((), jnp.float32)
    for gi, (pattern, rep) in enumerate(cfg.groups):
        gparams = params[f"group{gi}"]

        def body(carry, layer_params, pattern=pattern):
            h, aux = carry
            for pi, kind in enumerate(pattern):
                h, a = _apply_sublayer(cfg, kind, layer_params[f"pos{pi}"], h, positions, impl)
                aux = aux + a
            return (h, aux), None

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), gparams)
    return x, aux_total


def _embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _embed(cfg: ModelConfig, params: Params, tokens: jax.Array,
           patches: Optional[jax.Array]) -> jax.Array:
    x = _embed_tokens(cfg, params, tokens)
    if patches is not None:
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
    return with_logical(x, "batch", "seq", None)


def _logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return with_logical(logits, "batch", None, "vocab")


def forward(
    cfg: ModelConfig, params: Params, tokens: jax.Array,
    patches: Optional[jax.Array] = None, impl: str = "reference",
) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S_text); patches: (B, P, d) or None.
    Returns (logits (B, S_total, V), aux_loss)."""
    x = _embed(cfg, params, tokens, patches)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, aux = _run_groups(cfg, params, x, positions, impl)
    return _logits(cfg, params, x), aux


def loss_and_aux(
    cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
    impl: str = "reference",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy (f32), z-loss, MoE aux.  ``batch["tokens"]``:
    (B, S_text); optional ``batch["patches"]``: (B, P, d)."""
    tokens = batch["tokens"]
    patches = batch.get("patches")
    inputs = tokens[:, :-1]
    labels = tokens[:, 1:]
    logits, aux = forward(cfg, params, inputs, patches, impl)
    # predictions for text labels sit at the last (S_text - 1) positions
    logits = logits[:, -labels.shape[1]:, :].astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold).mean()
    z_loss = 1e-4 * (logz ** 2).mean()
    total = nll + z_loss + 0.01 * aux
    return total, {"nll": nll, "z_loss": z_loss, "moe_aux": aux}


# -------------------------------------------------------------------- decode
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    cache: Dict[str, Any] = {"t": jnp.zeros((), jnp.int32)}
    for gi, (pattern, rep) in enumerate(cfg.groups):
        g: Dict[str, Any] = {}
        for pi, kind in enumerate(pattern):
            if kind == "attn":
                g[f"pos{pi}"] = init_kv_cache(cfg, rep, batch, max_len, window=cfg.attn_window)
            elif kind == "rec":
                g[f"pos{pi}"] = rglru_init_state(cfg, rep, batch)
            elif kind == "rwkv":
                g[f"pos{pi}"] = rwkv_init_state(cfg, rep, batch)
            elif kind == "mamba":
                g[f"pos{pi}"] = mamba_init_state(cfg, rep, batch)
        cache[f"group{gi}"] = g
    return cache


def rewritten_leaves(cfg: ModelConfig) -> Tuple[str, ...]:
    """The decode cache's subtrees (``group<g>/pos<p>``) that every decode
    step rewrites whole: the recurrent state of each non-attention layer.
    Attention K/V gain one position a step, and ``t`` is a counter."""
    return tuple(f"group{gi}/pos{pi}" for gi, (pattern, _) in enumerate(cfg.groups)
                 for pi, kind in enumerate(pattern) if kind != "attn")


def cache_specs(cfg: ModelConfig, tp: int = 16) -> Dict:
    specs: Dict[str, Any] = {"t": ()}
    for gi, (pattern, rep) in enumerate(cfg.groups):
        g: Dict[str, Any] = {}
        for pi, kind in enumerate(pattern):
            if kind == "attn":
                g[f"pos{pi}"] = kv_cache_specs(cfg, tp)
            elif kind == "rec":
                g[f"pos{pi}"] = rglru_state_specs()
            elif kind == "rwkv":
                g[f"pos{pi}"] = rwkv_state_specs()
            elif kind == "mamba":
                g[f"pos{pi}"] = mamba_state_specs()
        specs[f"group{gi}"] = g
    return specs


def decode_step(
    cfg: ModelConfig, params: Params, token: jax.Array, cache: Dict,
) -> Tuple[jax.Array, Dict]:
    """token: (B, 1) int32.  Returns (logits (B, 1, V), updated cache)."""
    t = cache["t"]
    x = _embed_tokens(cfg, params, token)
    x = with_logical(x, "batch", None, None)
    new_cache: Dict[str, Any] = {"t": t + 1}

    for gi, (pattern, rep) in enumerate(cfg.groups):
        gparams = params[f"group{gi}"]
        gcache = cache[f"group{gi}"]

        def body(h, xs, pattern=pattern):
            layer_params, layer_cache = xs
            new_layer_cache = {}
            for pi, kind in enumerate(pattern):
                lp = layer_params[f"pos{pi}"]
                lc = layer_cache[f"pos{pi}"]
                hin = rms_norm(h, lp["norm1"], cfg.norm_eps)
                if kind == "attn":
                    y, ck, cv = attention_decode(
                        lp["attn"], hin, lc["k"], lc["v"], cfg, t, window=cfg.attn_window
                    )
                    new_layer_cache[f"pos{pi}"] = {"k": ck, "v": cv}
                elif kind == "rec":
                    y, hh, conv = rglru_decode_step(lp["rec"], hin, lc["h"], lc["conv"], cfg)
                    new_layer_cache[f"pos{pi}"] = {"h": hh, "conv": conv}
                elif kind == "rwkv":
                    y, S, x_last = rwkv_decode_step(lp["rwkv"], hin, lc["S"], lc["x_last"], cfg)
                    new_layer_cache[f"pos{pi}"] = {"S": S, "x_last": x_last}
                elif kind == "mamba":
                    y, ssm, conv = mamba_decode_step(lp["mamba"], hin, lc["ssm"], lc["conv"], cfg)
                    new_layer_cache[f"pos{pi}"] = {"ssm": ssm, "conv": conv}
                h = _residual(cfg, h, y)
                hin = rms_norm(h, lp["norm2"], cfg.norm_eps)
                if "moe" in lp:
                    y, _ = moe_apply(lp["moe"], hin, cfg, decode=True)
                else:
                    y = mlp_apply(lp["mlp"], hin, cfg)
                h = _residual(cfg, h, y)
            return h, new_layer_cache

        x, new_gcache = jax.lax.scan(body, x, (gparams, gcache))
        new_cache[f"group{gi}"] = new_gcache
    return _logits(cfg, params, x), new_cache


# ------------------------------------------------------------------- prefill
def prefill(
    cfg: ModelConfig, params: Params, tokens: jax.Array,
    patches: Optional[jax.Array] = None, impl: str = "reference",
) -> Tuple[jax.Array, Dict]:
    """Full-sequence pass that also builds the decode cache.

    For simplicity and HLO size, the cache is built by re-projecting K/V per
    layer inside the same scan (attention outputs are unchanged); recurrent
    states come from one extra step-scan over the final chunk for SSM layers.
    Returns (last-token logits (B, V), cache).
    """
    x = _embed(cfg, params, tokens, patches)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    cache: Dict[str, Any] = {"t": jnp.asarray(S, jnp.int32)}

    for gi, (pattern, rep) in enumerate(cfg.groups):
        gparams = params[f"group{gi}"]

        def body(carry, layer_params, pattern=pattern):
            h = carry
            new_layer_cache = {}
            for pi, kind in enumerate(pattern):
                lp = layer_params[f"pos{pi}"]
                hin = rms_norm(h, lp["norm1"], cfg.norm_eps)
                if kind == "attn":
                    y = attention_full(lp["attn"], hin, cfg, positions,
                                       window=cfg.attn_window, impl=impl)
                    new_layer_cache[f"pos{pi}"] = _kv_for_cache(cfg, lp["attn"], hin, positions)
                elif kind == "rec":
                    y = rglru_full(lp["rec"], hin, cfg, impl=impl)
                    new_layer_cache[f"pos{pi}"] = _rec_state_after(cfg, lp["rec"], hin)
                elif kind == "rwkv":
                    y = rwkv_scan_full(lp["rwkv"], hin, cfg, impl=impl)
                    new_layer_cache[f"pos{pi}"] = _rwkv_state_after(cfg, lp["rwkv"], hin)
                elif kind == "mamba":
                    y, new_layer_cache[f"pos{pi}"] = mamba_full(lp["mamba"], hin, cfg)
                h = _residual(cfg, h, y)
                hin = rms_norm(h, lp["norm2"], cfg.norm_eps)
                if "moe" in lp:
                    y, _ = moe_apply(lp["moe"], hin, cfg)
                else:
                    y = mlp_apply(lp["mlp"], hin, cfg)
                h = _residual(cfg, h, y)
            return h, new_layer_cache

        x, gcache = jax.lax.scan(body, x, gparams)
        cache[f"group{gi}"] = gcache
    logits = _logits(cfg, params, x[:, -1:, :])
    return logits[:, 0, :], cache


def _kv_for_cache(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array) -> Dict:
    from .attention import _split_heads
    from .layers import apply_rope, rope_angles

    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    k = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wk"]), hkv, dh)
    v = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wv"]), hkv, dh)
    if cfg.rope:
        cos, sin = rope_angles(positions, dh, cfg.rope_theta)
        k = apply_rope(k, cos, sin)
    if cfg.attn_window:
        k = k[:, -cfg.attn_window:]
        v = v[:, -cfg.attn_window:]
    return {"k": k, "v": v}


def _rec_state_after(cfg: ModelConfig, p: Dict, x: jax.Array) -> Dict:
    """Final RG-LRU state after the sequence (recompute via scan tail)."""
    from .rglru import _causal_conv, _gates

    b = x.shape[0]
    xr = jnp.einsum("bsd,de->bse", x, p["w_in_x"])
    prefix = jnp.zeros((b, cfg.rec.conv_width - 1, xr.shape[-1]), xr.dtype)
    conv_out = _causal_conv(xr, p["conv"], prefix)
    a, gx = _gates(p, conv_out)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    _, hh = jax.lax.associative_scan(combine, (a, gx), axis=1)
    return {"h": hh[:, -1], "conv": xr[:, -(cfg.rec.conv_width - 1):]}


def _rwkv_state_after(cfg: ModelConfig, p: Dict, x: jax.Array) -> Dict:
    from .rwkv6 import _head_split, _n_heads, _projections

    H, dh = _n_heads(cfg), cfg.rwkv.head_dim
    b, s, d = x.shape
    x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    _, k, v, w, _ = _projections(p, x, x_prev, cfg)
    k = _head_split(k, H, dh).astype(jnp.float32)
    v = _head_split(v, H, dh).astype(jnp.float32)
    w = _head_split(w, H, dh)

    def step(S, inputs):
        kt, vt, wt = inputs
        kv = kt[..., :, None] * vt[..., None, :]
        return wt[..., :, None] * S + kv, None

    S0 = jnp.zeros((b, H, dh, dh), jnp.float32)
    S, _ = jax.lax.scan(step, S0, (jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0), jnp.moveaxis(w, 1, 0)))
    return {"S": S, "x_last": x[:, -1]}
