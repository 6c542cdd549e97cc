"""Batched decode server with EasyCrash KV/recurrent-state persistence.

Serves a (reduced-by-default) architecture: prefill a batch of prompts,
decode greedily, and — the EasyCrash extension for inference — persist the
decode cache incrementally so a crashed server resumes sessions without
re-running prefill.  ``--inject-failure-at`` kills the server mid-stream to
demonstrate the recovery path: the restart reloads params + cache from the
arena, verifies by re-decoding the last committed token, and continues.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
      --prompts 4 --decode-steps 64 --inject-failure-at 32
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time
import uuid
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs import get_arch
from ..core.arena import NVMArena
from ..core.manager import EasyCrashManager, FlushPolicy, unflatten_state
from ..models import init_cache, init_params, rewritten_leaves, scaled_down
from ..telemetry import span, tracing
from .steps import make_decode_fn, make_prefill_step


class SimulatedFailure(RuntimeError):
    pass


def run(args) -> Dict[str, float]:
    with span("serve.session", session=uuid.uuid4().hex, prompts=args.prompts,
              prompt_len=args.prompt_len, decode_steps=args.decode_steps) as session:
        return _serve(args, session)


def _serve(args, session: span) -> Dict[str, float]:
    with span("serve.setup"):
        cfg = get_arch(args.arch)
        if not args.full_size:
            cfg = scaled_down(cfg, width=args.width)
        key = jax.random.PRNGKey(args.seed)
        params = init_params(cfg, key)
        prefill_fn = jax.jit(make_prefill_step(cfg))
        decode_fn = jax.jit(make_decode_fn(cfg), donate_argnums=(1,))

        os.makedirs(args.workdir, exist_ok=True)
        arena_dir = os.path.join(args.workdir, "serve_arena")
        try:
            arena = NVMArena.reattach(arena_dir)
            resumed = True
        except Exception:
            arena = NVMArena(backing_dir=arena_dir)
            resumed = False
        session.add(resumed=resumed)
        policy = FlushPolicy(leaves=("cache", "tokens"), every_steps=args.flush_every,
                             persist_mode=args.persist_mode)
        rewritten = tuple(f"cache/{leaf}" for leaf in rewritten_leaves(cfg))
        mgr = EasyCrashManager(arena, policy, rewritten=rewritten)

        max_len = args.prompt_len + args.decode_steps + 1
        prompts = jax.random.randint(
            jax.random.PRNGKey(7), (args.prompts, args.prompt_len), 0, cfg.vocab
        )

    if resumed and "__step__" in arena:
        start = int(arena.get("__step__"))
        print(f"[restore] resuming decode at step {start} from arena")
        flat = {n: arena.get(n) for n in arena.names() if not n.startswith("__")}
        state = unflatten_state(flat)
        cache = jax.tree.map(jnp.asarray, state["cache"])
        all_tokens = [jnp.asarray(state["tokens"])]
        token = all_tokens[-1][:, -1:]
    else:
        start = 0
        with span("serve.prefill"):
            logits, cache = prefill_fn(params, {"tokens": prompts})
            # right-size the cache for continued decoding
            full_cache = init_cache(cfg, args.prompts, max_len)
            cache = _splice_cache(cfg, full_cache, cache, args.prompt_len)
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            all_tokens = [prompts, token]
    session.add(**_cache_bytes(cache, rewritten))

    t0 = time.time()
    for step in range(start, args.decode_steps):
        with span("serve.decode", step=step + 1):
            token, cache = decode_fn(params, cache, token)
            # the span holds the step's program, not only its dispatch
            token.block_until_ready()
        all_tokens.append(token)
        # the state reaches the host only where a flush takes it, and before
        # the next step donates the cache
        if mgr.due(step + 1):
            mgr.maybe_flush(step + 1, _to_host(all_tokens, cache))
        if args.inject_failure_at and step + 1 == args.inject_failure_at:
            raise SimulatedFailure(f"injected failure at decode step {step + 1}")
    dt = time.time() - t0
    out = _to_host(all_tokens)["tokens"]
    stats = {
        "decode_steps": args.decode_steps - start,
        "tokens_per_s": (args.decode_steps - start) * args.prompts / max(dt, 1e-9),
        "blocks_written": mgr.stats.blocks_written,
        "bytes_written": mgr.stats.bytes_written,
        "resumed": resumed,
        "output_shape": list(out.shape),
    }
    print("[done]", stats)
    stats["tokens"] = out
    return stats


def _to_host(all_tokens, cache=None) -> Dict[str, object]:
    """The token buffer on the host, joined there (a join on the device
    would compile anew for every length), and the decode cache, if given,
    as device copies of its own, which the next step's donation leaves
    alone: a flush brings to the host only what it writes."""
    out = {} if cache is None else {"cache": _copy(cache)}
    with span("serve.host_copy") as copy:
        out["tokens"] = np.concatenate(jax.device_get(all_tokens), axis=1)
        if tracing():
            copy.add(nbytes=out["tokens"].nbytes)
    return out


#: one program that copies the whole decode cache on the device
_copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))


def _cache_bytes(cache, rewritten) -> Dict[str, int]:
    """The decode cache's bytes of recurrent state (the subtrees a step
    rewrites whole) and of attention K/V (the rest, but the counter)."""
    out = {"state_bytes": 0, "kv_bytes": 0}
    for g, layers in cache.items():
        if g == "t":
            continue
        for pos, leaves in layers.items():
            kind = "state_bytes" if f"cache/{g}/{pos}" in rewritten else "kv_bytes"
            out[kind] += sum(a.nbytes for a in jax.tree.leaves(leaves))
    return out


def _splice_cache(cfg, full_cache, prefill_cache, prompt_len: int):
    """Install prefill K/V into the right-sized decode cache."""
    def splice(dst, src):
        if dst.ndim >= 3 and src.ndim == dst.ndim and dst.shape != src.shape:
            # KV caches: (L, B, S, H, D) — copy the prefix
            n = min(src.shape[2], dst.shape[2])
            return jax.lax.dynamic_update_slice_in_dim(dst, src[:, :, :n], 0, axis=2)
        return src.astype(dst.dtype) if src.shape == dst.shape else dst

    out = jax.tree.map(splice, full_cache, prefill_cache)
    out["t"] = jnp.asarray(prompt_len, jnp.int32)
    return out


def main(argv=None) -> Dict[str, object]:
    """Serve, restart once after an injected failure, and return the last
    run's stats; ``stats["tokens"]`` is the final token buffer."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=64)
    ap.add_argument("--flush-every", type=int, default=8)
    ap.add_argument("--persist-mode", default="delta", choices=("delta", "full"),
                    help="flush granularity: delta_snapshot kernel (changed "
                         "blocks only) / whole-object rewrite")
    ap.add_argument("--workdir",
                    default=os.path.join(tempfile.gettempdir(), "repro_serve"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--inject-failure-at", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    try:
        return run(args)
    except SimulatedFailure as e:
        print(f"[failure] {e}; restarting...")
        args.inject_failure_at = 0
        return run(args)


if __name__ == "__main__":
    main()
