"""A whole run of each serve cell at a tiny size on the CPU, skipping only
the harness's look for a chip: sound, with its control, and with the timed
path broken underneath."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import calibrate
import run

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
DELTA = "stablelm-1.6b.serve.delta.8x64"
NOPERSIST = "stablelm-1.6b.serve.nopersist.8x64"
SEEDS = (2**31 + 5, 7, 2**32 + 11)
TINY = {"prompts": 4, "prompt_len": 8, "decode_steps": 8}
DELTA_TINY = {**TINY, "flush_every": 4}


def _manifest():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in m["configs"]:
        c["file"] = str((CHIP / "tests" / "tiny.json").relative_to(ROOT))
    return m


def _script(*args, cwd=ROOT, env=None):
    return subprocess.run([sys.executable, *args], cwd=cwd, capture_output=True,
                          text=True, timeout=300, env={**os.environ, **(env or {})})


def test_refuses_a_machine_without_a_tpu():
    r = _script("benchmarks/chip/run.py", "--workload", DELTA, "--seed", "1",
                "--seconds", "1", "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode == run.NO_CHIP
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _script("benchmarks/chip/run.py", "--workload", DELTA, "--seed", "1",
                "--seconds", "1", "--trace", "0", cwd=tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


@pytest.fixture(scope="module")
def limits():
    """Limits for the tiny size, set as the cells' are: between the
    program's largest reading and the control's smallest."""
    out = {}
    for cell, traffic in ((DELTA, DELTA_TINY), (NOPERSIST, TINY)):
        got = calibrate.calibrate(ROOT, cell, SEEDS, SEEDS, require_tpu=False,
                                  manifest=_manifest(), traffic_overrides=traffic)
        out[cell] = got
    return out


@pytest.mark.parametrize("cell", [DELTA, NOPERSIST])
def test_control_fails_where_the_program_passes(limits, cell):
    got = limits[cell]
    lo, hi = got["program_max"], got["control_min"]
    assert lo["sessions_differ"] == 0
    for k in hi:
        assert hi[k] >= 3 * lo[k], (k, lo[k], hi[k])
    if cell == DELTA:
        assert set(hi) == {"logit_gap", "kv_rel_err"}
        assert lo["arena_tokens_differ"] == lo["arena_step_off"] == lo["arena_images_differ"] == 0
    # the harness's own comparison, at the limits the runs below are held to
    for seed in SEEDS:
        assert all(c["ok"] for c in run.judge(got["program"][seed], _limits(got)))
        assert not all(c["ok"] for c in run.judge(got["control"][seed], _limits(got)))


def _limits(got):
    lo, hi = got["program_max"], got["control_min"]
    return {k: (lo[k] * hi[k]) ** 0.5 for k in hi}


def _run(cell, limits, trace=False, seed=SEEDS[0]):
    traffic = dict(DELTA_TINY if cell == DELTA else TINY)
    traffic["limits"] = _limits(limits[cell])
    return run.run_cell(ROOT, cell, seed, 0.2, trace, require_tpu=False,
                        manifest=_manifest(), traffic_overrides=traffic)


@pytest.mark.parametrize("cell", [DELTA, NOPERSIST])
@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(limits, cell, trace):
    r = _run(cell, limits, trace=trace, seed=123_456_789_012 % 2**33)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] >= TINY["prompts"] and r["failed"] == 0
    assert list(r)[-1] == "compared"
    if trace:
        assert "idle_share.serve" in r["metrics"]
        assert "mfu.serve" not in r["metrics"]  # no peak for a CPU
        assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert r["metrics"]["serve_tokens_per_s"]["value"] > 0
        assert r["metrics"]["setup_s"]["unit"] == "s"


def _decode_fault(kind):
    from repro.launch import steps

    real = steps.make_decode_fn

    def make(cfg):
        step = real(cfg)

        def faulty(params, cache, token):
            if kind == "state_unchanged":
                return token, cache
            nxt, new_cache = step(params, cache, token)
            if kind == "half_batch":
                nxt = nxt.at[nxt.shape[0] // 2:].set(0)
            elif kind == "token_altered":
                nxt = nxt.at[0, 0].set((nxt[0, 0] + 1) % cfg.vocab)
            return nxt, new_cache

        return faulty

    return make


@pytest.mark.parametrize("cell", [DELTA, NOPERSIST])
@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "token_altered"])
def test_broken_decode_is_not_correct(limits, cell, kind, monkeypatch):
    from repro.launch import serve

    monkeypatch.setattr(serve, "make_decode_fn", _decode_fault(kind))
    r = _run(cell, limits)
    assert r["correct"] is False, r["compared"]


def test_flush_that_writes_nothing_is_not_correct(limits, monkeypatch):
    import numpy as np

    from repro.core import delta_persist

    monkeypatch.setattr(delta_persist, "delta_block_mask",
                        lambda cur, live, block_bytes=64: np.zeros(
                            -(-np.asarray(live).nbytes // block_bytes), bool))
    r = _run(DELTA, limits)
    assert r["correct"] is False
    assert r["compared"]["kv_rel_err"]["value"] > r["compared"]["kv_rel_err"]["limit"]


def test_reference_draws_the_programs_weights():
    import jax
    import numpy as np

    from modules import load_module
    from repro.configs import get_arch
    from repro.models import init_params, scaled_down

    ref = load_module(CHIP / "references" / "dense_decoder.py", "bench_ref_dense_decoder")
    cfg = json.loads((CHIP / "tests" / "tiny.json").read_text())
    seed = 2**31 + 3
    w = ref.init_weights(ref.Dims.from_config(cfg), seed)
    p = init_params(scaled_down(get_arch(cfg["arch"]), width=cfg["hidden_size"]),
                    jax.random.PRNGKey(seed))
    g = p["group0"]["pos0"]
    pairs = [(p["embed"], w["embed"]), (p["unembed"], w["unembed"]),
             (p["final_norm"], w["final_norm"]), (g["norm1"], w["layers"]["norm1"]),
             (g["norm2"], w["layers"]["norm2"])]
    pairs += [(g["attn"][k], w["layers"][k]) for k in ("wq", "wk", "wv", "wo")]
    pairs += [(g["mlp"][k], w["layers"][k]) for k in ("w_gate", "w_up", "w_down")]
    for a, b in pairs:
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
