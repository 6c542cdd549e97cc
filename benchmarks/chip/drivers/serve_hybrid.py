"""Driver ``serve_hybrid``: the ``serve`` driver's sessions, for a hybrid of
Mamba-2 and attention layers (Granite-4.0-H).

The unit of work, the closed loop and the end-to-end metric are the
``serve`` driver's (imported from it). What differs:

- the configuration check covers the layer pattern, the Mamba-2 keys and
  Granite's scalars;
- the objects a delta flush masks are each attention position's K and V
  (with the position counter and the step); the recurrent state (SSM state
  and conv window) is written whole, so no mask is warmed or counted for it;
- the FLOP count is ``flops_hybrid``'s;
- correctness, after the window, against ``references/<reference>.py``:
  ``logit_gap`` and ``sessions_differ`` as in ``serve``; with persistence
  on, in the last session's arena, ``kv_rel_err`` (per attention layer, K
  and V against the reference's), ``state_rel_err`` (per Mamba layer, the
  SSM state and the conv window against the reference's after the last
  flushed position), ``arena_tokens_differ`` and ``arena_step_off`` as in
  ``serve``, and ``arena_images_differ``: sessions whose persisted cache
  objects, every one, are not byte for byte the last session's.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path
from typing import Dict, List

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import flops  # noqa: E402
import flops_hybrid  # noqa: E402
from modules import load_module  # noqa: E402

serve = load_module(HERE / "drivers" / "serve.py", "bench_driver_serve")

SPANS = serve.SPANS
run_unit = serve.run_unit
end_to_end = serve.end_to_end
flushes_per_session = serve.flushes_per_session


def _program_layer_types(cfg) -> List[str]:
    return ["attention" if k == "attn" else k
            for pattern, rep in cfg.groups for _ in range(rep) for k in pattern]


def _check_program_config(ctx) -> None:
    """The program must run the configuration this file states, with the
    layer period as its one group of layers (the arena's object names
    follow the group's positions)."""
    from repro.configs import get_arch
    from repro.models import scaled_down

    c = ctx.config
    cfg = get_arch(c["arch"])
    if not c["full_size"]:
        cfg = scaled_down(cfg, width=c["hidden_size"])
    m = cfg.mamba
    have = {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "shared_intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
            "rms_norm_eps": cfg.norm_eps, "torch_dtype": cfg.dtype,
            "tie_word_embeddings": cfg.tie_embeddings, "hidden_act": cfg.activation,
            "layer_types": _program_layer_types(cfg),
            "position_embedding_type": "rope" if cfg.rope else "nope",
            "attention_multiplier": cfg.attn_scale,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "num_local_experts": 0 if cfg.moe is None else cfg.moe.num_experts,
            "mamba_d_state": m.d_state, "mamba_d_conv": m.d_conv, "mamba_expand": m.expand,
            "mamba_d_head": m.head_dim, "mamba_n_heads": m.expand * cfg.d_model // m.head_dim,
            "mamba_n_groups": m.n_groups, "mamba_chunk_size": m.chunk,
            # the program's projections: a conv bias, no other bias
            "mamba_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False}
    off = {k: (v, c[k]) for k, v in have.items() if v != c[k]}
    if cfg.embed_std != c["assumed"]["embed_std"]:
        off["assumed.embed_std"] = (cfg.embed_std, c["assumed"]["embed_std"])
    if off:
        raise ValueError(f"program config differs from {c['name']}: {off}")
    period = serve.reference(ctx).Dims.from_config(c).period
    if len(cfg.groups) != 1 or list(cfg.groups[0][0]) != [
            "attn" if k == "attention" else k for k in period]:
        raise ValueError(f"the program's layer groups {cfg.groups} are not the period {period}")


def masked_object_bytes(ctx) -> List[int]:
    """Bytes of each object a delta flush masks: K and V of each attention
    position of the layer period (stacked over its repeats), the cache's
    position counter, the persisted step."""
    c, t = ctx.config, ctx.traffic
    period = serve.reference(ctx).Dims.from_config(c).period
    repeats = len(c["layer_types"]) // len(period)
    head_dim = c["hidden_size"] // c["num_attention_heads"]
    kv = (repeats * t["prompts"] * serve._max_len(t) * c["num_key_value_heads"]
          * head_dim * 2)
    return [kv, kv] * period.count("attention") + [4, 8]


def setup(ctx) -> None:
    """As the ``serve`` driver's set-up: one session at the cell's sizes
    with flushing off, then the delta kernel on each masked object's size."""
    from repro.core.delta_persist import delta_block_mask
    from repro.launch import serve as program

    _check_program_config(ctx)
    warm = ctx.workdir / "warmup"
    program.main(serve._argv(ctx, warm, flush_every=10 ** 9))
    if ctx.traffic["persist_mode"] == "delta" and flushes_per_session(ctx.traffic) > 1:
        for n in sorted(set(masked_object_bytes(ctx))):
            zero = np.zeros(n, np.uint8)
            delta_block_mask(zero, zero, 64)
    shutil.rmtree(warm, ignore_errors=True)


def account(ctx, results: List[Dict]) -> Dict[str, float]:
    t = ctx.traffic
    n = len(results)
    generated = sum(int(r["tokens"].shape[0] * (r["tokens"].shape[1] - t["prompt_len"]))
                    for r in results)
    delta_flushes = 0
    if t["persist_mode"] == "delta":
        delta_flushes = n * max(flushes_per_session(t) - 1, 0)
    return {
        "attempted": n * t["prompts"],
        "failed": sum(t["prompts"] for r in results if r["resumed"]),
        "sessions": n,
        "generated_tokens": generated,
        "decode_steps": n * t["decode_steps"],
        "model_flops": n * flops_hybrid.serve_session_flops(
            ctx.config, t["prompts"], t["prompt_len"], t["decode_steps"]),
        "delta_mask_bytes": delta_flushes * sum(
            flops.delta_mask_bytes(b) for b in masked_object_bytes(ctx)),
    }


def _cache_objects(workdir: Path) -> Dict[str, np.ndarray]:
    """The decode cache's persisted objects in a session's arena, by name,
    each in its own dtype."""
    import ml_dtypes

    arena = workdir / "serve_arena"
    manifest = json.loads((arena / "manifest.json").read_text())["objects"]
    out = {}
    for name, dtype in manifest.items():
        if name.startswith("cache/group"):
            a = np.load(arena / (name.replace("/", "__") + ".npy"))
            out[name] = a.view(ml_dtypes.bfloat16) if dtype == "bfloat16" else a
    return out


def _flat(caches) -> Dict[str, object]:
    """The reference's caches under the program's object names."""
    return {f"cache/group0/pos{pi}/{leaf}": a
            for pi, cache in enumerate(caches) for leaf, a in cache.items()}


def _rel_errs(ref, got: Dict, want: Dict, positions: int) -> Dict[str, float]:
    """The largest per-layer relative error of K/V (the first ``positions``)
    and of the recurrent state (SSM state, conv window). An object that was
    not persisted, or not at its shape, reads 1: the error of zeros."""
    kv, state = [0.0], [0.0]
    for name, w in want.items():
        is_kv = name.endswith(("/k", "/v"))
        g = got.get(name)
        if g is not None and is_kv:
            g = g[:, :, :positions]
        err = (float(np.asarray(ref.rel_err(g, w)).max())
               if g is not None and g.shape == w.shape else 1.0)
        (kv if is_kv else state).append(err)
    return {"kv_rel_err": max(kv), "state_rel_err": max(state)}


def readings(ctx, results: List[Dict]) -> Dict[str, float]:
    """The numbers the harness compares with the traffic's limits, for the
    window's sessions."""
    ref = serve.reference(ctx)
    t = ctx.traffic
    last = results[-1]
    served = last["tokens"]
    p = t["prompt_len"]
    dims = ref.Dims.from_config(ctx.config)
    weights = ref.init_weights(dims, ctx.seed)
    logits, caches = ref.forward(dims, weights, served[:, :-1], p - 1, served.shape[1] - p)
    out = {
        "logit_gap": float(np.asarray(ref.logit_gaps(logits, served[:, p:])).max()),
        "sessions_differ": sum(not np.array_equal(r["tokens"], served) for r in results),
    }
    del logits, weights
    if flushes_per_session(t) > 0:
        objects = _cache_objects(last["workdir"])
        out.update(_rel_errs(ref, objects, _flat(caches), served.shape[1] - 1))
        arena = last["workdir"] / "serve_arena"
        a_tokens = np.load(arena / "tokens.npy")
        last_flush = flushes_per_session(t) * t["flush_every"]
        want = served[:, :p + 1 + last_flush]
        out["arena_tokens_differ"] = (int(np.sum(a_tokens != want))
                                      if a_tokens.shape == want.shape else int(want.size))
        out["arena_step_off"] = abs(int(np.load(arena / "__step__.npy")) - last_flush)
        out["arena_images_differ"] = 0
        for r in results[:-1]:
            other = _cache_objects(r["workdir"])
            out["arena_images_differ"] += other.keys() != objects.keys() or any(
                not np.array_equal(other[k], objects[k]) for k in objects)
    return out


def control_readings(ctx, results: List[Dict]) -> Dict[str, float]:
    """The same numbers for the control: the reference one precision down
    (``fp8``) in the program's place, at every position of the last
    session's prompts and served tokens: the gap of the token the control
    puts first, and its decode cache against the float32 reference's."""
    ref = serve.reference(ctx)
    p = ctx.traffic["prompt_len"]
    served = results[-1]["tokens"]
    n_out = served.shape[1] - p
    dims = ref.Dims.from_config(ctx.config)
    weights = ref.init_weights(dims, ctx.seed)
    c_logits, c_caches = ref.forward(dims, weights, served[:, :-1], p - 1, n_out,
                                     precision="fp8")
    chosen = np.asarray(c_logits.argmax(axis=-1))
    del c_logits
    logits, caches = ref.forward(dims, weights, served[:, :-1], p - 1, n_out)
    out = {"logit_gap": float(np.asarray(ref.logit_gaps(logits, chosen)).max())}
    if flushes_per_session(ctx.traffic) > 0:
        out.update(_rel_errs(ref, _flat(c_caches), _flat(caches), served.shape[1] - 1))
    return out
