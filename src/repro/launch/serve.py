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
from ..core.manager import EasyCrashManager, FlushPolicy, flatten_state
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
                             async_flush=False, persist_mode=args.persist_mode)
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
        from ..core.manager import unflatten_state

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
    mgr.close()
    stats["tokens"] = out
    return stats


def _to_host(all_tokens, cache=None) -> Dict[str, object]:
    """The decode cache, if given, and the token buffer on the host. The
    buffer's pieces are joined there: a join on the device would compile
    anew for every length."""
    with span("serve.host_copy") as copy:
        host = {} if cache is None else {"cache": jax.tree.map(np.asarray, cache)}
        host["tokens"] = np.concatenate(jax.device_get(all_tokens), axis=1)
        if tracing():
            copy.add(nbytes=sum(a.nbytes for a in jax.tree.leaves(host)))
    return host


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


def fleet_report(stats: Dict[str, float], args) -> Dict[str, dict]:
    """Project this server's *measured* serving process onto a replica fleet.

    The single-process run measures the two quantities the fleet simulator
    needs from the real system: the per-step decode time (service rate) and
    the flush traffic to the arena's files (``bytes_written`` -> ``t_s`` via
    :func:`~repro.core.efficiency.persist_overhead_fraction`).  Everything
    else — arrivals, failures, recovery policy — is simulated, so the same
    binary answers "what would this server's goodput/p99 look like across N
    replicas under paper-like failure rates?".
    """
    from ..core import (
        POLICIES,
        ArrivalProcess,
        FleetConfig,
        PoissonTrace,
        RecomputeProfile,
        ServiceModel,
        SystemConfig,
        fleet_frontier,
        persist_overhead_fraction,
    )

    steps = max(int(stats["decode_steps"]), 1)
    step_time = args.prompts / max(stats["tokens_per_s"], 1e-9)
    t_s = persist_overhead_fraction(stats["bytes_written"] / steps, step_time)
    # decode sessions are S1-dominant (the KV cache is the session and it is
    # what we persist); the tail mirrors the decode campaign's shape
    profile = RecomputeProfile.from_fractions(
        "serve", {"S1": 0.9, "S2": 0.06, "S3": 0.02, "S4": 0.02},
        extra_iters_hist=((2, 3), (8, 1)),
    )
    service_s = args.decode_steps * step_time
    rate = args.fleet_rate
    if rate <= 0:  # auto: offer ~80% of fleet capacity at the measured speed
        rate = 0.8 * args.fleet_replicas / max(service_s, 1e-3)
    cfg = FleetConfig(
        n_replicas=args.fleet_replicas,
        arrival=ArrivalProcess(rate=rate, amplitude=0.3),
        service=ServiceModel(mean_s=max(service_s, 1e-3), sigma=0.6,
                             prefill_s=max(args.prompt_len * step_time, 1e-3)),
        trace=PoissonTrace(mtbf=args.fleet_mtbf),
        system=SystemConfig(mtbf=args.fleet_mtbf, t_chk=30.0,
                            nvm_restore_time=2.0),
        slo_latency=4.0 * max(service_s, 1e-3),
        queue_cap=48,
        horizon=args.fleet_horizon,
        t_s=t_s,
        t_iter=step_time,
        seed=args.seed,
    )
    print(f"[fleet] measured t_s={t_s:.4f} step={step_time*1e3:.2f}ms "
          f"service={service_s:.2f}s; {cfg.n_replicas} replicas, "
          f"mtbf={cfg.trace.mtbf:.0f}s, horizon={cfg.horizon:.0f}s")
    doc = fleet_frontier(cfg, profile)
    for policy in POLICIES:
        p = doc["policies"][policy]
        print(f"[fleet] {policy:10s} goodput={p['goodput']:.3f}rps "
              f"loss={p['dropped']/max(p['arrived'],1):.3f} "
              f"slo={p['slo_violation_frac']:.3f} "
              f"p99={p['latency_p99']:.2f}s fails={p['n_failures']}")
    return doc["policies"]


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
    ap.add_argument("--persist-mode", default="delta",
                    choices=("auto", "delta", "full"),
                    help="flush granularity: arena byte diff / delta_snapshot "
                         "kernel (changed blocks only) / whole-object rewrite")
    ap.add_argument("--workdir",
                    default=os.path.join(tempfile.gettempdir(), "repro_serve"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--inject-failure-at", type=int, default=0)
    ap.add_argument("--fleet", action="store_true",
                    help="after serving, project the measured step time and "
                         "persist traffic onto a replica fleet under "
                         "failures (repro.core.fleetsim policy comparison)")
    ap.add_argument("--fleet-replicas", type=int, default=4)
    ap.add_argument("--fleet-rate", type=float, default=0.0,
                    help="fleet offered load, requests/s "
                         "(<= 0: auto, ~80%% of measured fleet capacity)")
    ap.add_argument("--fleet-mtbf", type=float, default=900.0,
                    help="per-replica MTBF, seconds")
    ap.add_argument("--fleet-horizon", type=float, default=1800.0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    try:
        stats = run(args)
    except SimulatedFailure as e:
        print(f"[failure] {e}; restarting...")
        args.inject_failure_at = 0
        stats = run(args)
    if args.fleet:
        fleet_report(stats, args)
    return stats


if __name__ == "__main__":
    main()
