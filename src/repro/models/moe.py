"""Mixture-of-Experts FFN: a top-k router over every expert, and the part of
the layer that the experts held here give.

The router (``_route``) chooses ``top_k`` of all ``num_experts`` by a
float32 softmax; ``norm_topk_prob`` renormalises the chosen weights to sum
to 1 (Qwen-MoE, Llama-4) or keeps the softmax's own (DeepSeek-V2); no
configuration scales them (DeepSeek-V2-Lite's ``routed_scaling_factor`` is
1). Under expert parallelism (``ep_size`` > 1) this chip holds
``experts_held`` consecutive experts from ``first_held``: a (token, expert)
pair whose expert lies on another chip adds nothing here,
and nothing stands in for the absent chips or their exchange. Three
dispatches share the router:

* ``sort``: argsort tokens by expert, scatter into per-expert capacity
  buffers, one batched einsum over stacked expert weights, scatter back with
  gate weighting.  Over-capacity tokens are dropped (standard Switch/GShard
  semantics; capacity_factor controls slack).  Buffers shard over "experts"
  (EP) or "expert_ff" (TP) per the config.  Every expert is held.
* ``dropless``: the held experts' pairs sorted by expert, each expert's run
  padded to whole tiles of ``TILE`` rows, and the tiles the routing fills
  computed one at a time with their expert's weights: no pair is dropped and
  no expert computes a token not routed to it.
* ``dense`` (oracle): every held expert processes every token, combined by
  gate weight (0 where not routed).  O(E/k) more FLOPs.

The server's prefill (``transformer.prefill``) takes the configuration's
``impl``; its decode steps (``decode=True``) take ``dense``.

Shared experts (Qwen-MoE, Llama-4, DeepSeek-V2) are a plain gated MLP added
once to the routed output.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import with_logical
from .config import ModelConfig
from .layers import activation_fn, dtype_of, mlp_apply, mlp_params, mlp_specs, normal_init

#: rows of a ``dropless`` tile
TILE = 512


def _experts(key, n: int, ids, shape, scale, dt) -> jax.Array:
    """``(n, len(ids), *shape)``: expert ``e``'s weights drawn from
    ``fold_in(key, e)``, so that every share holds the same experts as the
    whole layer."""
    return jnp.stack([normal_init(jax.random.fold_in(key, e), (n, *shape), scale, dt)
                      for e in ids], axis=1)


def moe_params(cfg: ModelConfig, key, n: int) -> Dict:
    assert cfg.moe is not None
    m = cfg.moe
    d = cfg.d_model
    dt = dtype_of(cfg)
    keys = jax.random.split(key, 5)
    s_in = d ** -0.5
    s_out = m.d_ff_expert ** -0.5
    held = range(m.first_held, m.first_held + m.experts_held)
    p = {
        "router": normal_init(keys[0], (n, d, m.num_experts), s_in, jnp.float32),
        "w_gate": _experts(keys[1], n, held, (d, m.d_ff_expert), s_in, dt),
        "w_up": _experts(keys[2], n, held, (d, m.d_ff_expert), s_in, dt),
        "w_down": _experts(keys[3], n, held, (m.d_ff_expert, d), s_out, dt),
    }
    if m.d_ff_shared:
        p["shared"] = mlp_params(cfg, keys[4], n, d_ff=m.d_ff_shared)
    return p


def moe_specs(cfg: ModelConfig) -> Dict:
    m = cfg.moe
    ep = m.expert_parallel
    e_ax = "experts" if ep else None
    f_ax = None if ep else "expert_ff"
    p = {
        "router": (None, "fsdp", None),
        "w_gate": (None, e_ax, "fsdp", f_ax),
        "w_up": (None, e_ax, "fsdp", f_ax),
        "w_down": (None, e_ax, f_ax, "fsdp"),
    }
    if m.d_ff_shared:
        p["shared"] = mlp_specs()
    return p


def _route(x2d: jax.Array, router: jax.Array, m) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x2d: (T, d) -> (gates (T,k), experts (T,k) int32, aux losses)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    gates_all = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(gates_all, m.top_k)
    if m.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance + router-z auxiliary losses (GShard / ST-MoE)
    density = jnp.mean(jax.nn.one_hot(experts[:, 0], m.num_experts), axis=0)
    density_prob = jnp.mean(gates_all, axis=0)
    lb_loss = m.num_experts * jnp.sum(density * density_prob)
    z_loss = m.router_z_loss * jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return gates, experts.astype(jnp.int32), lb_loss + z_loss


def _expert_mlp(w_gate, w_up, w_down, h, act):
    """h: (E, C, d) -> (E, C, d) through per-expert gated MLP."""
    g = jnp.einsum("ecd,edf->ecf", h, w_gate)
    u = jnp.einsum("ecd,edf->ecf", h, w_up)
    g = with_logical(act(g) * u, "experts", None, "expert_ff")
    return jnp.einsum("ecf,efd->ecd", g, w_down)


def _held_index(experts: jax.Array, m) -> jax.Array:
    """Each chosen expert's index among the held ones; ``experts_held``
    where it is held on another chip."""
    local = experts - m.first_held
    return jnp.where((local >= 0) & (local < m.experts_held), local, m.experts_held)


def _dropless(p: Dict, x2d: jax.Array, gates: jax.Array, held_idx: jax.Array, m, act):
    """The held experts' part for their routed tokens only, none dropped:
    pairs sorted by expert, each expert's run padded to whole tiles, the
    tiles the routing fills computed one at a time (later tiles skipped).
    Returns (y (T, d) float32, pairs dropped)."""
    T, d = x2d.shape
    held = m.experts_held
    k = gates.shape[1]
    flat_e = held_idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    st = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)[order]
    sg = gates.reshape(-1)[order]
    counts = jnp.zeros((held + 1,), jnp.int32).at[flat_e].add(1)[:held]
    tiles = -(-counts // TILE)
    tile_end = jnp.cumsum(tiles)
    run_start = jnp.cumsum(counts) - counts
    # a token chooses distinct experts: at most min(k, held) of its pairs are held here
    max_tiles = -(-T * min(k, held) // TILE) + held
    size = max_tiles * TILE
    e = jnp.minimum(se, held - 1)
    slot = (tile_end[e] - tiles[e]) * TILE + jnp.arange(se.size, dtype=jnp.int32) - run_start[e]
    dropped = jnp.sum((se < held) & (slot >= size)).astype(jnp.int32)
    slot = jnp.where((se < held) & (slot < size), slot, size)
    tok = jnp.zeros((size + 1,), jnp.int32).at[slot].set(st)[:size]
    gate = jnp.zeros((size + 1,), jnp.float32).at[slot].set(sg)[:size]
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(max_tiles), side="right"), held - 1)

    def tile(acc, i):
        def compute(acc):
            rows = jax.lax.dynamic_slice_in_dim(tok, i * TILE, TILE)
            g = jax.lax.dynamic_slice_in_dim(gate, i * TILE, TILE)
            ei = tile_expert[i]
            h = x2d[rows]
            u = act(h @ p["w_gate"][ei]) * (h @ p["w_up"][ei])
            return acc.at[rows].add((u @ p["w_down"][ei]).astype(jnp.float32) * g[:, None])

        return jax.lax.cond(i < tile_end[-1], compute, lambda a: a, acc), None

    y, _ = jax.lax.scan(tile, jnp.zeros((T, d), jnp.float32), jnp.arange(max_tiles))
    return y, dropped


def moe_apply(p: Dict, x: jax.Array, cfg: ModelConfig,
              decode: bool = False) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (y, stats): the router's auxiliary loss (``aux``), the
    (token, expert) pairs routed to the experts held here (``pairs``), and
    those of them no expert computed (``dropped``: capacity overflow of
    ``sort``, else 0), the last two int32.

    ``decode=True`` forces the dense path: a decode step is weight-bandwidth
    bound (every expert's weights stream from HBM regardless of routing), so
    capacity buffers would only add dropping artefacts for zero savings.
    """
    m = cfg.moe
    act = activation_fn(cfg.activation)
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    gates, experts, aux = _route(x2d, p["router"], m)
    held_idx = _held_index(experts, m)
    T = b * s
    dropped = jnp.zeros((), jnp.int32)

    if m.impl == "dense" or decode:
        # oracle: all held experts on all tokens
        h = jnp.einsum("td,edf->tef", x2d, p["w_gate"])
        u = jnp.einsum("td,edf->tef", x2d, p["w_up"])
        y_all = jnp.einsum("tef,efd->ted", act(h) * u, p["w_down"])
        combine = jnp.zeros((T, m.experts_held + 1), x.dtype)
        combine = combine.at[jnp.arange(T)[:, None], held_idx].add(gates.astype(x.dtype))
        y = jnp.einsum("ted,te->td", y_all, combine[:, :m.experts_held])
    elif m.impl == "dropless":
        y, dropped = _dropless(p, x2d, gates, held_idx, m, act)
        y = y.astype(x.dtype)
    else:
        # sort-based capacity dispatch, optionally in shard-local groups
        G = max(1, m.dispatch_groups)
        assert T % G == 0, (T, G)
        tg = T // G
        cap = int(max(1, round(tg * m.top_k / m.num_experts * m.capacity_factor)))

        def dispatch(xg, gg, eg):
            """One group's tokens through the experts.  xg: (tg, d)."""
            flat_e = eg.reshape(-1)                       # (tg*k,)
            flat_t = jnp.repeat(jnp.arange(tg), m.top_k)  # token of each slot
            flat_g = gg.reshape(-1)
            order = jnp.argsort(flat_e, stable=True)
            se, st, sg = flat_e[order], flat_t[order], flat_g[order]
            pos = jnp.arange(se.shape[0], dtype=jnp.int32)
            run_start = jnp.full((m.num_experts,), se.shape[0], jnp.int32).at[se].min(pos)
            pos_in_e = pos - run_start[se]
            keep = pos_in_e < cap
            slot = jnp.where(keep, se * cap + pos_in_e, m.num_experts * cap)
            buf = jnp.zeros((m.num_experts * cap + 1, d), x.dtype)
            buf = buf.at[slot].set(xg[st])
            h = buf[: m.num_experts * cap].reshape(m.num_experts, cap, d)
            return h, (slot, st, sg, keep)

        def combine(yb, meta):
            slot, st, sg, keep = meta
            yb = jnp.concatenate([yb.reshape(m.num_experts * cap, d),
                                  jnp.zeros((1, d), x.dtype)], axis=0)
            contrib = yb[slot] * sg[:, None].astype(x.dtype)
            return jnp.zeros((tg, d), x.dtype).at[st].add(
                jnp.where(keep[:, None], contrib, 0.0))

        if G == 1:
            h, meta = dispatch(x2d, gates, experts)
            h = with_logical(h, "experts", None, None)
            yb = _expert_mlp(p["w_gate"], p["w_up"], p["w_down"], h, act)
            y = combine(yb, meta)
        else:
            xg = with_logical(x2d.reshape(G, tg, d), "batch", None, None)
            gg = gates.reshape(G, tg, m.top_k)
            eg = experts.reshape(G, tg, m.top_k)
            h, meta = jax.vmap(dispatch)(xg, gg, eg)      # (G, E, cap, d)
            h = with_logical(h, "batch", "experts", None, None)
            gge = jnp.einsum("gecd,edf->gecf", h, p["w_gate"])
            uge = jnp.einsum("gecd,edf->gecf", h, p["w_up"])
            hh = with_logical(act(gge) * uge, "batch", "experts", None, "expert_ff")
            yb = jnp.einsum("gecf,efd->gecd", hh, p["w_down"])
            y = jax.vmap(combine)(yb, meta).reshape(T, d)
        dropped = jnp.sum(~meta[3]).astype(jnp.int32)

    if m.d_ff_shared:
        y = y + mlp_apply(p["shared"], x, cfg).reshape(T, d)
    stats = {"aux": aux, "pairs": jnp.sum(held_idx < m.experts_held).astype(jnp.int32),
             "dropped": dropped}
    return with_logical(y.reshape(b, s, d), "batch", "seq", None), stats
