"""Bring-up smoke for one TPU chip: both hot paths through their entry points.

    python chip_smoke.py

One process, one chip, no child processes.  Phases, each printing its own
lines; any failure exits non-zero before the last line is printed:

1. device  — platform, device kind, device count, JAX version;
2. kernel  — the ``delta_snapshot`` dirty-block mask, compiled (its HLO must
   hold a ``tpu_custom_call``), on byte views of cache-sized buffers with
   even and odd block counts, equal to ``block_diff_mask``;
3. numerics — the float32 summation and division rounding that engine
   parity rests on;
4. campaign — ``run_workflow`` to a persist plan on one suite app at
   ``BENCH_SIZES``, then per HPC app a small campaign on the ``vec`` engine
   and on the ``ref`` oracle, which must agree record for record;
5. server  — ``repro.launch.serve`` on stablelm-1.6b at full width with
   delta persistence: an uninterrupted run, a run killed halfway that
   resumes from its arena without prefill and must end on the same token
   buffer, and a whole-object-rewrite run for the byte comparison.

The last line of standard output is one JSON object naming the device.
Without a TPU the script exits non-zero at once.  Times printed here come
from one smoke run on whatever chip it finds; they are not a benchmark.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
import traceback
import warnings
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: scratch space for the server's arenas (git-ignored, emptied per run)
OUT = ROOT / ".smoke_out"

HPC_APPS = ("cg", "heat", "kmeans", "mg", "montecarlo", "pagerank", "sor")
WORKFLOW_APP = "kmeans"
WORKFLOW_TESTS = 32
PARITY_TESTS = 24

#: 64-byte blocks per kernel buffer: one even count, one odd (8 MiB each)
KERNEL_BLOCKS = (131_072, 131_073)

DECODE_STEPS = 32
SERVE_ARGS = ["--arch", "stablelm-1.6b", "--full-size", "--prompts", "4",
              "--prompt-len", "32", "--decode-steps", str(DECODE_STEPS),
              "--flush-every", "8"]


def phase_device():
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def phase_kernel():
    """Delta masks on byte views with ``block_elems=64`` (the arena's cache
    block), even and odd block counts, a partial tail block on the odd one,
    and a few sparse random byte edits."""
    import jax

    from repro.core.blocks import block_diff_mask
    from repro.kernels.delta_snapshot import dirty_block_mask

    rng = np.random.default_rng(0)
    for nb in KERNEL_BLOCKS:
        nbytes = nb * 64 - (5 if nb % 2 else 0)
        prev = rng.integers(0, 256, nbytes, dtype=np.uint8)
        cur = prev.copy()
        edits = rng.choice(nbytes, size=37, replace=False)
        cur[edits] ^= rng.integers(1, 256, edits.size, dtype=np.uint8)
        hlo = dirty_block_mask.lower(cur, prev, block_elems=64).compile().as_text()
        if "tpu_custom_call" not in hlo:
            raise AssertionError("delta_snapshot did not lower to a tpu_custom_call")
        got = np.asarray(jax.block_until_ready(dirty_block_mask(cur, prev, block_elems=64)))
        t0 = time.perf_counter()
        jax.block_until_ready(dirty_block_mask(cur, prev, block_elems=64))
        dt = time.perf_counter() - t0
        want = block_diff_mask(prev, cur, 64)
        if got.shape != want.shape or not np.array_equal(got.astype(bool), want):
            raise AssertionError(f"delta mask != block_diff_mask at {nb} blocks")
        dirty = np.unique(edits // 64).size
        if int(got.sum()) != dirty:
            raise AssertionError(f"{int(got.sum())} dirty blocks flagged, {dirty} edited")
        print(f"[kernel] delta_snapshot bytes={nbytes} blocks={nb} dirty={dirty} "
              f"equal=ok tpu_custom_call=True call_s={dt:.6f}")


def phase_numerics():
    """The chip's float32 rounding that engine parity rests on.  A serial
    region reduces one vector and a batched hook reduces rows of a lane
    stack: ``tree_sum`` must round alike for a 1-D vector, one row and eight
    rows (asserted); whether a plain ``jnp.sum`` does, and how many float32
    quotients differ from the correctly rounded ones, is printed — the
    reasons cg and kmeans sum through ``tree_sum`` and cg divides on the
    device on both paths."""
    import jax
    import jax.numpy as jnp

    from repro.hpc.common import tree_sum

    rng = np.random.default_rng(0)
    a = rng.standard_normal((8, 48 * 48)).astype(np.float32)
    b = rng.standard_normal((8, 48 * 48)).astype(np.float32)

    def by_shape(reduce):
        """Row dot products summed as 1-D vectors, one row, eight rows."""
        fn = jax.jit(lambda x, y: reduce(x * y))
        one_d = np.stack([np.asarray(fn(a[i], b[i])) for i in range(8)])
        one_row = np.stack([np.asarray(fn(a[i:i + 1], b[i:i + 1]))[0] for i in range(8)])
        return one_d, one_row, np.asarray(fn(a, b))

    t1, tr, t8 = by_shape(tree_sum)
    if not (np.array_equal(t1, tr) and np.array_equal(tr, t8)):
        raise AssertionError("tree_sum rounds differently for 1-D, one-row and eight-row sums")
    s1, sr, s8 = by_shape(lambda x: jnp.sum(x, axis=-1))
    num = rng.standard_normal(100_000).astype(np.float32)
    den = (rng.standard_normal(100_000) * 10.0 ** rng.integers(-3, 4, 100_000)).astype(np.float32)
    quot = np.asarray(jax.jit(jnp.divide)(num, den))
    exact = (num.astype(np.float64) / den.astype(np.float64)).astype(np.float32)
    print(f"[numerics] tree_sum_1d_row_8rows_equal=ok "
          f"jnp_sum_1d_eq_row={np.array_equal(s1, sr)} "
          f"jnp_sum_row_eq_8rows={np.array_equal(sr, s8)} "
          f"f32_div_differs_from_correctly_rounded={int(np.sum(quot != exact))}/{num.size}")


def phase_campaign():
    """Workflow to a persist plan on one app, then vec/ref record parity on
    every HPC app.  Returns the apps whose parity failed."""
    from repro.core import CrashTester, PersistPlan, WorkflowConfig, run_workflow
    from repro.core.crash_tester import records_match
    from repro.core.trace_cache import WindowTraceCache
    from repro.hpc.suite import BENCH_SIZES, default_cache, get_app

    app = get_app(WORKFLOW_APP, **BENCH_SIZES[WORKFLOW_APP])
    t0 = time.perf_counter()
    wf = run_workflow(app, WorkflowConfig(
        n_tests=WORKFLOW_TESTS, cache=default_cache(app), n_workers=1))
    dt = time.perf_counter() - t0
    fr = wf.baseline_campaign.class_fractions()
    print(f"[workflow] app={WORKFLOW_APP} tests={wf.tests_executed} seconds={dt:.3f} "
          f"plan_objects={list(wf.plan.objects)} "
          f"plan_regions={sorted(wf.plan.region_freq.items())} "
          + " ".join(f"{k}={fr.get(k, 0.0):.4f}" for k in ("S1", "S2", "S3", "S4")))

    failed = []
    for name in HPC_APPS:
        runs, secs = {}, {}
        for engine in ("vec", "ref"):
            app = get_app(name, **BENCH_SIZES[name])
            tester = CrashTester(
                app, PersistPlan.none(), default_cache(app), seed=123,
                engine=engine, trace_cache=WindowTraceCache(0, 0),
            )
            t0 = time.perf_counter()
            runs[engine] = tester.run_campaign(PARITY_TESTS, n_workers=1)
            secs[engine] = time.perf_counter() - t0
        ok = records_match(runs["ref"].records, runs["vec"].records)
        if not ok:
            failed.append(name)
        print(f"[parity] app={name} tests={PARITY_TESTS} vec_s={secs['vec']:.3f} "
              f"ref_s={secs['ref']:.3f} parity={'ok' if ok else 'FAIL'}")
    return failed


def phase_server():
    """Uninterrupted vs killed-and-resumed decode with delta persistence,
    plus a whole-object-rewrite run: the blocks each mode marks dirty and the
    bytes the arena's files receive."""
    from repro.launch.serve import main as serve_main

    def serve(tag, *extra):
        workdir = OUT / f"serve_{tag}"
        shutil.rmtree(workdir, ignore_errors=True)
        return serve_main([*SERVE_ARGS, "--workdir", str(workdir), *extra])

    half = DECODE_STEPS // 2
    ref = serve("delta", "--persist-mode", "delta")
    crashed = serve("crash", "--persist-mode", "delta", "--inject-failure-at", str(half))
    full = serve("full", "--persist-mode", "full")
    if not crashed["resumed"] or crashed["decode_steps"] != DECODE_STEPS - half:
        raise AssertionError(f"restart did not resume from the arena: {crashed}")
    if not np.array_equal(crashed["tokens"], ref["tokens"]):
        raise AssertionError("resumed token buffer != uninterrupted token buffer")
    if not np.array_equal(full["tokens"], ref["tokens"]):
        raise AssertionError("full-rewrite run decoded different tokens")
    print(f"[server] tokens_shape={list(ref['tokens'].shape)} "
          f"tokens_per_s={ref['tokens_per_s']:.3f} "
          f"delta_blocks_written={ref['blocks_written']} "
          f"full_rewrite_blocks_written={full['blocks_written']} "
          f"delta_bytes_written={ref['bytes_written']} "
          f"full_rewrite_bytes_written={full['bytes_written']} "
          f"resumed_at_step={half} resumed_tokens_equal=ok")


def _campaign_phase():
    with warnings.catch_warnings():
        # the campaign machinery must not warn its way past a device
        # failure; NumPy's floating-point notes from blow-up lanes are the
        # apps' own and classify as S3
        warnings.simplefilter("error", RuntimeWarning)
        warnings.filterwarnings("ignore", message=".*encountered in",
                                category=RuntimeWarning)
        failed = phase_campaign()
    if failed:
        raise AssertionError(f"vec != ref records on {failed}")


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: JAX found {jax.devices()[0].platform!r}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = phase_device()
    print(f"[device] compile_cache={cache_dir}")
    shutil.rmtree(OUT, ignore_errors=True)
    ok = True
    try:
        # every phase runs even after another failed, so one run shows all
        for phase in (phase_kernel, phase_numerics, _campaign_phase, phase_server):
            try:
                phase()
            except Exception:  # noqa: BLE001 - any phase failure fails the smoke
                traceback.print_exc()
                print(f"[{phase.__name__}] FAILED")
                ok = False
    finally:
        shutil.rmtree(OUT, ignore_errors=True)
    cached = sum(f.stat().st_size for f in Path(cache_dir).rglob("*") if f.is_file())
    print(f"[device] compile_cache_bytes={cached}")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
