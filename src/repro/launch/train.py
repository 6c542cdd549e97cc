"""Production training driver: EasyCrash + multilevel C/R + failure injection.

Runs a (reduced-by-default) architecture for N steps on the local device(s),
wiring together every fault-tolerance layer this framework provides:

  * EasyCrash flushes of the *critical* state subset (params + step — the
    selection the crash campaigns find; Adam moments re-warm) to a
    host-local NVM arena, in the step loop, every ``--flush-every`` steps;
  * multilevel checkpoints at the Young interval stretched by measured
    recomputability (MTBF' = MTBF / (1 - R));
  * deterministic, seekable data (restart needs only the step counter);
  * ``--inject-failure-every K`` kills the loop mid-step every K steps; the
    driver then restores via EasyCrash -> checkpoint -> fresh, with a
    loss-based acceptance verification guarding the EasyCrash path.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --steps 200 --inject-failure-every 60 --workdir /tmp/ec_train
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointConfig, CheckpointManager
from ..compile_cache import enable_compile_cache
from ..configs import get_arch
from ..core.arena import NVMArena
from ..core.manager import EasyCrashManager, FlushPolicy
from ..data import DataConfig, SyntheticLMStream
from ..models import scaled_down
from .steps import init_train_state, make_train_step


class SimulatedFailure(RuntimeError):
    pass


def _to_host(tree):
    return jax.tree.map(lambda x: np.asarray(x), tree)


def build(args):
    cfg = get_arch(args.arch)
    if not args.full_size:
        cfg = scaled_down(cfg, width=args.width)
    data_cfg = DataConfig(
        seq_len=args.seq, global_batch=args.batch, vocab=cfg.vocab,
        frontend_tokens=cfg.frontend_tokens, d_model=cfg.d_model,
    )
    step_fn = jax.jit(
        make_train_step(cfg, peak_lr=args.lr, total_steps=args.steps),
        donate_argnums=(0,),
    )
    return cfg, data_cfg, step_fn


def run(args) -> Dict[str, float]:
    cfg, data_cfg, step_fn = build(args)
    os.makedirs(args.workdir, exist_ok=True)
    arena_dir = os.path.join(args.workdir, "arena")
    ckpt = CheckpointManager(CheckpointConfig(
        local_dir=os.path.join(args.workdir, "ckpt_local"),
        remote_dir=os.path.join(args.workdir, "ckpt_remote"),
    ))

    def checkpoint_save(step: int, state) -> None:
        ckpt.save(step, _to_host(state))

    def checkpoint_restore():
        got = ckpt.restore()
        if got is None:
            return None
        return got[0], got[1]

    try:
        arena = NVMArena.reattach(arena_dir)
        print(f"[restore] reattached arena with {len(list(arena.names()))} objects")
    except Exception:
        arena = NVMArena(backing_dir=arena_dir)

    policy = FlushPolicy(
        leaves=("params", "step"), every_steps=args.flush_every,
        persist_mode=args.persist_mode,
    )
    mgr = EasyCrashManager(
        arena, policy,
        checkpoint_save=checkpoint_save,
        checkpoint_restore=checkpoint_restore,
        mtbf=args.mtbf, t_chk=args.t_chk,
        recomputability=args.recomputability, step_time=1.0,
    )

    init_state = init_train_state(cfg, jax.random.PRNGKey(args.seed))

    def verify(candidate, step) -> bool:
        """Acceptance verification: one forward loss must be finite and sane."""
        try:
            stream0 = SyntheticLMStream(data_cfg, 0, 1, start_step=step)
            _, batch = next(stream0)
            stream0.close()
            from ..models import loss_and_aux

            loss, _ = loss_and_aux(
                cfg, jax.tree.map(jnp.asarray, candidate["params"]),
                {k: jnp.asarray(v) for k, v in batch.items()},
            )
            ok = bool(np.isfinite(float(loss)) and float(loss) < args.verify_loss_max)
            print(f"[verify] step={step} loss={float(loss):.3f} -> {'ACCEPT' if ok else 'REJECT'}")
            return ok
        except Exception as e:  # noqa: BLE001
            print(f"[verify] failed: {e}")
            return False

    state_host, start_step, source = mgr.restore(_to_host(init_state), verify=verify)
    print(f"[restore] source={source} step={start_step}")
    state = jax.tree.map(jnp.asarray, state_host)
    state["step"] = jnp.asarray(start_step, jnp.int32)

    stream = SyntheticLMStream(data_cfg, 0, 1, start_step=start_step)
    losses = []
    t0 = time.time()
    step = start_step
    try:
        while step < args.steps:
            _, batch = next(stream)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, metrics = step_fn(state, batch)
            step += 1
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({(time.time()-t0)/max(1,step-start_step):.2f}s/step)")
            host_state = _to_host(state)
            mgr.maybe_flush(step, host_state)
            mgr.maybe_checkpoint(step, host_state)
            if args.inject_failure_every and step % args.inject_failure_every == 0 \
                    and step < args.steps:
                raise SimulatedFailure(f"injected failure at step {step}")
    finally:
        stream.close()

    ckpt.close()
    stats = {
        "final_step": step,
        "final_loss": losses[-1] if losses else float("nan"),
        "flushes": mgr.stats.flushes_issued,
        "blocks_written": mgr.stats.blocks_written,
        "bytes_written": mgr.stats.bytes_written,
        "checkpoints": mgr.stats.checkpoints_taken,
        "easycrash_restores": mgr.stats.easycrash_restores,
        "checkpoint_restores": mgr.stats.checkpoint_restores,
        "restore_source": source,
    }
    print("[done]", stats)
    return stats


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (TPU pods); default reduced")
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir",
                    default=os.path.join(tempfile.gettempdir(), "repro_train"))
    ap.add_argument("--flush-every", type=int, default=1)
    ap.add_argument("--persist-mode", default="delta", choices=("delta", "full"),
                    help="flush granularity: delta_snapshot kernel (changed "
                         "blocks only) / whole-object rewrite")
    ap.add_argument("--mtbf", type=float, default=300.0)
    ap.add_argument("--t-chk", type=float, default=5.0)
    ap.add_argument("--recomputability", type=float, default=0.82)
    ap.add_argument("--verify-loss-max", type=float, default=20.0)
    ap.add_argument("--inject-failure-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--max-restarts", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    restarts = 0
    while True:
        try:
            run(args)
            return
        except SimulatedFailure as e:
            restarts += 1
            print(f"[failure] {e} (restart {restarts})")
            if restarts > args.max_restarts:
                raise


if __name__ == "__main__":
    main()
