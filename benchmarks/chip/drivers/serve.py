"""Driver ``serve``: sessions of the persisting decode server, back to back.

Unit of work: one call of ``repro.launch.serve.main`` with the traffic's
batch, prompt length, decode steps, flush cadence and persist mode, in a
fresh work directory (a reused one would resume from its arena). A closed
loop: the next session starts when the last has returned.

Correctness, after the window, against ``references/<reference>.py``:

- ``logit_gap``: over every served token of the last session (all its
  rows), the widest gap by which the reference's logit of the served token
  lies below the reference's best logit at that position (greedy decoding);
- ``sessions_differ``: sessions whose served tokens differ from the last
  session's (same seed, same prompts: exact);
- with persistence on, the last session's arena: ``kv_rel_err``, per layer
  the relative error of the persisted K and V against the reference's, the
  largest; ``arena_tokens_differ``, tokens in the persisted token buffer
  unequal to those served; ``arena_step_off``, the persisted step's
  distance from the last decode step; ``arena_images_differ``, sessions
  whose persisted K/V bytes differ from the last session's.
"""
from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import flops  # noqa: E402
from modules import load_module  # noqa: E402

#: host spans for naming idle gaps in the traced run (and the flush metric)
SPANS = {
    "serve.run": "repro.launch.serve:run",
    "serve.init_params": "repro.launch.serve:init_params",
    "manager.maybe_flush": "repro.core.manager:EasyCrashManager.maybe_flush",
    "delta.mask": "repro.core.delta_persist:delta_block_mask",
    "arena.persist": "repro.core.arena:NVMArena._persist_to_backing",
}

#: the arena's file for each persisted object ("/" becomes "__")
KV_FILES = ("cache__group0__pos0__k.npy", "cache__group0__pos0__v.npy")


def reference(ctx):
    name = ctx.config["reference"]
    return load_module(HERE / "references" / f"{name}.py", f"bench_ref_{name}")


def _max_len(t: Dict) -> int:
    return t["prompt_len"] + t["decode_steps"] + 1


def _argv(ctx, workdir: Path, flush_every: int) -> List[str]:
    c, t = ctx.config, ctx.traffic
    size = ["--full-size"] if c["full_size"] else ["--width", str(c["hidden_size"])]
    return ["--arch", c["arch"], *size,
            "--prompts", str(t["prompts"]), "--prompt-len", str(t["prompt_len"]),
            "--decode-steps", str(t["decode_steps"]), "--flush-every", str(flush_every),
            "--persist-mode", t["persist_mode"], "--workdir", str(workdir),
            "--seed", str(ctx.seed)]


def _check_program_config(ctx) -> None:
    """The program must run the configuration this file states."""
    from repro.configs import get_arch
    from repro.models import scaled_down

    c = ctx.config
    cfg = get_arch(c["arch"])
    if not c["full_size"]:
        cfg = scaled_down(cfg, width=c["hidden_size"])
    have = {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "intermediate_size": cfg.d_ff,
            "vocab_size": cfg.vocab, "rope_theta": cfg.rope_theta,
            "layer_norm_eps": cfg.norm_eps, "torch_dtype": cfg.dtype,
            "tie_word_embeddings": cfg.tie_embeddings}
    off = {k: (v, c[k]) for k, v in have.items() if v != c[k]}
    if off:
        raise ValueError(f"program config differs from {c['name']}: {off}")


def flushed_object_bytes(ctx) -> List[int]:
    """Bytes of each object a delta flush masks: K and V of the cache, the
    cache's position counter, the persisted step. (The token buffer grows
    between flushes, so the arena rewrites it whole and masks nothing.)"""
    c, t = ctx.config, ctx.traffic
    kv = (c["num_hidden_layers"] * t["prompts"] * _max_len(t)
          * c["num_key_value_heads"] * c["head_dim"] * 2)
    return [kv, kv, 4, 8]


def flushes_per_session(t: Dict) -> int:
    return t["decode_steps"] // t["flush_every"]


def setup(ctx) -> None:
    """Warm every shape the window uses: one session at the cell's sizes
    with flushing off (prefill, decode, and every eager op of the loop),
    then, where flushes mask, the delta kernel on each masked object's size.
    Writes nothing to the arena."""
    from repro.launch import serve

    _check_program_config(ctx)
    warm = ctx.workdir / "warmup"
    serve.main(_argv(ctx, warm, flush_every=10 ** 9))
    if ctx.traffic["persist_mode"] == "delta" and flushes_per_session(ctx.traffic) > 1:
        from repro.core.delta_persist import delta_block_mask

        for n in sorted(set(flushed_object_bytes(ctx))):
            zero = np.zeros(n, np.uint8)
            delta_block_mask(zero, zero, 64)
    shutil.rmtree(warm, ignore_errors=True)


def run_unit(ctx, i: int) -> Dict:
    from repro.launch import serve

    workdir = ctx.workdir / f"session{i}"
    t0 = time.perf_counter()
    stats = serve.main(_argv(ctx, workdir, ctx.traffic["flush_every"]))
    return {"tokens": np.asarray(stats["tokens"]), "seconds": time.perf_counter() - t0,
            "workdir": workdir, "resumed": bool(stats["resumed"])}


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
        "generated_tokens": generated,
        "decode_steps": n * t["decode_steps"],
        "model_flops": n * flops.serve_session_flops(
            ctx.config, t["prompts"], t["prompt_len"], t["decode_steps"]),
        "delta_mask_bytes": delta_flushes * sum(
            flops.delta_mask_bytes(b) for b in flushed_object_bytes(ctx)),
    }


def end_to_end(ctx, results: List[Dict]) -> Dict[str, float]:
    return {"serve_tokens_per_s": ctx.counters["generated_tokens"] / ctx.window_s}


def _load_kv(workdir: Path):
    import ml_dtypes

    arena = workdir / "serve_arena"
    return [np.load(arena / f).view(ml_dtypes.bfloat16) for f in KV_FILES]


def _persists(t: Dict) -> bool:
    return flushes_per_session(t) > 0


def readings(ctx, results: List[Dict]) -> Dict[str, float]:
    """The numbers the harness compares with the traffic's limits, for the
    window's sessions."""
    ref = reference(ctx)
    t = ctx.traffic
    last = results[-1]
    served = last["tokens"]
    p = t["prompt_len"]
    dims = ref.Dims.from_config(ctx.config)
    weights = ref.init_weights(dims, ctx.seed)
    kv = _load_kv(last["workdir"]) if _persists(t) else None
    logits, _, errs = ref.forward(dims, weights, served[:, :-1], p - 1, served.shape[1] - p,
                                  kv_cmp=kv)
    out = {
        "logit_gap": float(np.asarray(ref.logit_gaps(logits, served[:, p:])).max()),
        "sessions_differ": sum(not np.array_equal(r["tokens"], served) for r in results),
    }
    if kv is not None:
        arena = last["workdir"] / "serve_arena"
        a_tokens = np.load(arena / "tokens.npy")
        last_flush = flushes_per_session(t) * t["flush_every"]
        want = served[:, :p + 1 + last_flush]
        out["kv_rel_err"] = float(np.asarray(errs).max())
        out["arena_tokens_differ"] = (int(np.sum(a_tokens != want))
                                      if a_tokens.shape == want.shape else int(want.size))
        out["arena_step_off"] = abs(int(np.load(arena / "__step__.npy")) - last_flush)
        out["arena_images_differ"] = sum(
            any(not np.array_equal(a, b) for a, b in zip(_load_kv(r["workdir"]), kv))
            for r in results[:-1])
    return out


def control_readings(ctx, results: List[Dict]) -> Dict[str, float]:
    """The same numbers for the control: the reference in float8 (e4m3) in
    the program's place, at every position of the last session's prompts
    and served tokens: the gap of the token the control puts first, and its
    K/V against the float32 reference's."""
    ref = reference(ctx)
    p = ctx.traffic["prompt_len"]
    served = results[-1]["tokens"]
    n_out = served.shape[1] - p
    dims = ref.Dims.from_config(ctx.config)
    weights = ref.init_weights(dims, ctx.seed)
    c_logits, c_kv, _ = ref.forward(dims, weights, served[:, :-1], p - 1, n_out, precision="fp8")
    chosen = np.asarray(c_logits.argmax(axis=-1))
    del c_logits
    logits, _, errs = ref.forward(dims, weights, served[:, :-1], p - 1, n_out, kv_cmp=c_kv)
    out = {"logit_gap": float(np.asarray(ref.logit_gaps(logits, chosen)).max())}
    if _persists(ctx.traffic):
        out["kv_rel_err"] = float(np.asarray(errs).max())
    return out
