"""Driver ``serve_mla``: the ``serve`` driver's sessions, for a decoder with
latent attention and one chip's share of routed experts (DeepSeek-V2-Lite).

The closed loop and the end-to-end metric are the ``serve`` driver's
(imported from it). What differs:

- the configuration check covers every published key of the architecture
  (latent attention, YaRN, the router, the shared experts, the dense first
  layer) and the expert share (the experts held, the published count, the
  rank);
- the unit of work keeps the session's count of (token, held expert) pairs
  (``expert_pairs``), and the FLOP count is ``flops_mla``'s, from it;
- the warm-up is one whole session at the cell's sizes with a single
  flush, into a directory of its own: the flush's device copies of each
  cache object compile there (in ``full`` mode no object is masked, so
  there is no mask to warm);
- correctness, after the window, against ``references/<reference>.py``:
  ``logit_gap`` and ``sessions_differ`` as in ``serve``; with persistence
  on, in the last session's arena, ``latent_rel_err`` (per layer, the
  persisted ``c_kv`` and ``k_pe`` against the reference's; an object not
  persisted, or not at its shape, reads 1), ``arena_tokens_differ`` and
  ``arena_step_off`` as in ``serve``, and ``arena_images_differ``: sessions
  whose persisted cache objects, every one, are not byte for byte the last
  session's.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List

import jax
import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import flops_mla  # noqa: E402
from modules import load_module  # noqa: E402

serve = load_module(HERE / "drivers" / "serve.py", "bench_driver_serve")

SPANS = serve.SPANS
end_to_end = serve.end_to_end
flushes_per_session = serve.flushes_per_session


def _program_config(cfg) -> Dict:
    """The configuration file's keys, as the program's configuration has
    them."""
    a, m, y = cfg.mla, cfg.moe, cfg.yarn
    return {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "torch_dtype": cfg.dtype,
        "tie_word_embeddings": cfg.tie_embeddings, "hidden_act": cfg.activation,
        "kv_lora_rank": a.kv_lora_rank, "qk_nope_head_dim": a.qk_nope_head_dim,
        "qk_rope_head_dim": a.qk_rope_head_dim, "v_head_dim": a.v_head_dim,
        "rope_scaling": {"beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "factor": y.factor, "mscale": y.mscale,
                         "mscale_all_dim": y.mscale_all_dim,
                         "original_max_position_embeddings": y.original_max_position,
                         "type": "yarn"},
        "first_k_dense_replace": m.first_k_dense, "moe_intermediate_size": m.d_ff_expert,
        "n_shared_experts": m.d_ff_shared / m.d_ff_expert,
        "num_experts_per_tok": m.top_k, "norm_topk_prob": m.norm_topk_prob,
        "n_routed_experts": m.experts_held,
        "published": {"n_routed_experts": m.num_experts},
        "expert_parallel": {"ep_size": m.ep_size, "ep_rank": m.ep_rank},
        # what the program's layers are: no q LoRA, no attention bias, a
        # softmax router choosing greedily among all experts, in no groups,
        # its weights unscaled, experts in every layer after the dense ones
        "routed_scaling_factor": 1.0, "q_lora_rank": None, "attention_bias": False, "scoring_func": "softmax",
        "topk_method": "greedy", "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
    }


def _check_program_config(ctx) -> None:
    """The program must run the configuration this file states, with the
    dense layers, then the expert layers, as its two groups of layers (the
    arena's object names follow the groups)."""
    from repro.configs import get_arch
    from repro.models import scaled_down

    c = ctx.config
    cfg = get_arch(c["arch"])
    if not c["full_size"]:
        cfg = scaled_down(cfg, width=c["hidden_size"])
    if cfg.mla is None or cfg.moe is None or cfg.yarn is None:
        raise ValueError(f"the program's {c['arch']} has no latent attention, experts or YaRN")
    off = {k: (v, c.get(k, "missing")) for k, v in _program_config(cfg).items()
           if k not in c or v != c[k]}
    if cfg.embed_std != c["assumed"]["embed_std"]:
        off["assumed.embed_std"] = (cfg.embed_std, c["assumed"]["embed_std"])
    dense = c["first_k_dense_replace"]
    if cfg.groups != ((("attn",), dense), (("attn",), c["num_hidden_layers"] - dense)):
        off["groups"] = (cfg.groups, "the dense layers, then the expert layers")
    if off:
        raise ValueError(f"program config differs from {c['name']}: {off}")


def run_unit(ctx, i: int) -> Dict:
    from repro.launch import serve as program

    workdir = ctx.workdir / f"session{i}"
    t0 = time.perf_counter()
    stats = program.main(serve._argv(ctx, workdir, ctx.traffic["flush_every"]))
    return {"tokens": np.asarray(stats["tokens"]), "seconds": time.perf_counter() - t0,
            "workdir": workdir, "resumed": bool(stats["resumed"]),
            "expert_pairs": stats.get("expert_pairs")}


def setup(ctx) -> None:
    """One whole session at the cell's sizes with one flush, at its last
    step, into a work directory that is then removed: prefill, decode, every
    eager op of the loop and the flush's device copies of each cache object
    compile here (in ``full`` mode every flush fetches the same objects
    whole, so one flush warms them all)."""
    from repro.launch import serve as program

    _check_program_config(ctx)
    warm = ctx.workdir / "warmup"
    program.main(serve._argv(ctx, warm, ctx.traffic["decode_steps"]))
    shutil.rmtree(warm, ignore_errors=True)


def account(ctx, results: List[Dict]) -> Dict[str, float]:
    t = ctx.traffic
    n = len(results)
    generated = sum(int(r["tokens"].shape[0] * (r["tokens"].shape[1] - t["prompt_len"]))
                    for r in results)
    return {
        "attempted": n * t["prompts"],
        "failed": sum(t["prompts"] for r in results if r["resumed"]),
        "sessions": n,
        "generated_tokens": generated,
        "decode_steps": n * t["decode_steps"],
        "model_flops": sum(flops_mla.serve_session_flops(
            ctx.config, t["prompts"], t["prompt_len"], t["decode_steps"],
            expert_pairs=r["expert_pairs"]) for r in results),
    }


def _cache_objects(workdir: Path) -> Dict[str, np.ndarray]:
    """The decode cache's persisted layer objects in a session's arena, by
    name, each in its own dtype."""
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
    return {f"cache/group{gi}/pos0/{leaf}": a
            for gi, cache in enumerate(caches) for leaf, a in cache.items()}


def _latent_rel_err(ref, got: Dict, want: Dict, positions: int) -> float:
    """The largest per-layer relative error of ``c_kv`` and ``k_pe`` over
    the first ``positions``. An object that was not persisted, or not at its
    shape, reads 1: the error of zeros."""
    errs = [0.0]
    for name, w in want.items():
        g = got.get(name)
        g = None if g is None else g[:, :, :positions]
        errs.append(float(np.asarray(ref.rel_err(g, w)).max())
                    if g is not None and g.shape == w.shape else 1.0)
    return max(errs)


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
        out["latent_rel_err"] = _latent_rel_err(ref, objects, _flat(caches), served.shape[1] - 1)
        del caches
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
    puts first, and its latent cache against the float32 reference's."""
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
    c_caches = jax.device_get(c_caches)  # off the chip while the reference runs
    logits, caches = ref.forward(dims, weights, served[:, :-1], p - 1, n_out)
    out = {"logit_gap": float(np.asarray(ref.logit_gaps(logits, chosen)).max())}
    if flushes_per_session(ctx.traffic) > 0:
        out["latent_rel_err"] = _latent_rel_err(ref, _flat(c_caches), _flat(caches),
                                                served.shape[1] - 1)
    return out
