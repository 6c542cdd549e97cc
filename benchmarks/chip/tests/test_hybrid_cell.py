"""A whole run of the hybrid serve cell at a tiny size on the CPU (the
program's scaled-down granite-4.0-h-micro, ``tiny_hybrid.json``), skipping
only the harness's look for a chip: sound, with its control, and with the
recurrent state's path broken underneath."""
import json
from pathlib import Path

import pytest

import calibrate
import run

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
CELL = "granite-4.0-h-micro.serve.delta.8x1024"
SEEDS = (2**31 + 5, 7, 2**32 + 11)
# two flushes a session, as in the cell; the prompt is not a multiple of
# the tiny model's SSD chunk (8)
TINY = {"prompts": 4, "prompt_len": 12, "decode_steps": 8, "flush_every": 4}
HYBRID_METRICS = {"state_persist_ms_per_step.hybrid", "host_copy_ms_per_step.hybrid",
                  "prefill_ms_per_session.hybrid", "decode_ms_per_step.hybrid",
                  "idle_share.hybrid", "mfu.hybrid"}


def _manifest():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in m["configs"]:
        c["file"] = str((CHIP / "tests" / "tiny_hybrid.json").relative_to(ROOT))
    return m


@pytest.fixture(scope="module")
def limits():
    """Limits for the tiny size, set as the cell's are: between the
    program's largest reading and the control's smallest."""
    return calibrate.calibrate(ROOT, CELL, SEEDS, SEEDS, require_tpu=False,
                               manifest=_manifest(), traffic_overrides=TINY)


def _limits(got):
    """Geometric middle of the two readings; where the program reads far
    below the control (the tiny model's logit gap can read 0), the middle
    of a thirtieth of the control's reading and the reading itself."""
    lo, hi = got["program_max"], got["control_min"]
    return {k: (max(lo[k], hi[k] / 30) * hi[k]) ** 0.5 for k in hi}


def test_control_fails_where_the_program_passes(limits):
    lo, hi = limits["program_max"], limits["control_min"]
    assert set(hi) == {"logit_gap", "kv_rel_err", "state_rel_err"}
    for k in ("kv_rel_err", "state_rel_err"):
        assert hi[k] >= 3 * lo[k], (k, lo[k], hi[k])
    assert lo["sessions_differ"] == lo["arena_tokens_differ"] == 0
    assert lo["arena_step_off"] == lo["arena_images_differ"] == 0
    for seed in SEEDS:
        assert all(c["ok"] for c in run.judge(limits["program"][seed], _limits(limits)))
        assert not all(c["ok"] for c in run.judge(limits["control"][seed], _limits(limits)))


def _run(limits, trace=False, seed=SEEDS[0]):
    return run.run_cell(ROOT, CELL, seed, 0.2, trace, require_tpu=False, manifest=_manifest(),
                        traffic_overrides={**TINY, "limits": _limits(limits)})


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(limits, trace):
    r = _run(limits, trace=trace, seed=123_456_789_012 % 2**33)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] >= TINY["prompts"] and r["failed"] == 0
    assert list(r)[-1] == "compared"
    if trace:
        # every per-layer metric of the cell reads something, but the share
        # of a peak that a CPU does not have
        assert set(r["metrics"]) == HYBRID_METRICS - {"mfu.hybrid"}
        assert r["metrics"]["state_persist_ms_per_step.hybrid"]["value"] > 0
        assert r["device"]["busy_s"] > 0
    else:
        assert r["metrics"]["serve_tokens_per_s"]["value"] > 0
        assert r["metrics"]["setup_s"]["unit"] == "s"


def _recurrent(cfg):
    from repro.models import rewritten_leaves

    return [leaf.split("/") for leaf in rewritten_leaves(cfg)]


def test_decode_that_leaves_the_ssm_state_unchanged_is_not_correct(limits, monkeypatch):
    from repro.launch import serve, steps

    real = steps.make_decode_fn

    def make(cfg):
        step = real(cfg)

        def faulty(params, cache, token):
            nxt, new = step(params, cache, token)
            for g, p in _recurrent(cfg):
                new[g][p]["ssm"] = cache[g][p]["ssm"]
            return nxt, new

        return faulty

    monkeypatch.setattr(serve, "make_decode_fn", make)
    r = _run(limits)
    assert r["correct"] is False
    assert r["compared"]["state_rel_err"]["value"] > r["compared"]["state_rel_err"]["limit"]


def test_flush_that_skips_the_recurrent_state_is_not_correct(limits, monkeypatch):
    from repro.core.manager import EasyCrashManager

    real = EasyCrashManager._flush_now

    def skipping(self, step, payload):
        kept = {n: a for n, a in payload.items()
                if not any(self._match(n, leaf) for leaf in self.rewritten)}
        assert len(kept) < len(payload)
        return real(self, step, kept)

    monkeypatch.setattr(EasyCrashManager, "_flush_now", skipping)
    r = _run(limits)
    assert r["correct"] is False
    assert r["compared"]["state_rel_err"]["value"] == 1.0  # nothing persisted


def test_state_persisted_from_the_wrong_step_is_not_correct(limits, monkeypatch):
    """Each flush persists the recurrent state the previous flush took
    (K/V, tokens and step are the flush's own)."""
    from repro.launch import serve

    real = serve._to_host
    last = {}

    def stale(all_tokens, cache=None):
        host = real(all_tokens, cache)
        if cache is not None:
            fresh = {g: {p: dict(v) for p, v in layers.items()}
                     for g, layers in host["cache"].items() if g != "t"}
            for g, layers in last.get("cache", {}).items():
                for p, leaves in layers.items():
                    if "ssm" in leaves:
                        host["cache"][g][p] = leaves
            last["cache"] = fresh
        return host

    monkeypatch.setattr(serve, "_to_host", stale)
    r = _run(limits)
    assert r["correct"] is False
    assert r["compared"]["state_rel_err"]["value"] > r["compared"]["state_rel_err"]["limit"]
    assert r["compared"]["kv_rel_err"]["value"] <= r["compared"]["kv_rel_err"]["limit"]


def test_hybrid_flops_match_a_hand_count():
    import flops_hybrid

    cfg = json.loads((CHIP / "configs" / "granite-4.0-h-micro.json").read_text())
    # a Mamba layer, per token: in_proj 2048 x 8512 and out_proj 4096 x 2048
    # (multiply-adds, 2 each); the conv, 4 taps on 4352 channels; the state
    # update, 3 per element of 64 heads x 64 x 128, and its read-out, 2
    mamba = 2 * 2048 * 8512 + 2 * 4096 * 2048 + 2 * 4352 * 4 + 5 * 64 * 64 * 128
    assert mamba == 54_298_624
    # an attention layer: q and o 2048 x 2048, k and v 2048 x 512, then q.k
    # and p.v over c positions of 32 heads x 64
    attn = 2 * 2048 * (2 * 2048 + 2 * 512)
    mlp = 3 * 2 * 2048 * 8192
    head = 2 * 2048 * 100_352
    assert flops_hybrid.hybrid_token_flops(cfg, 1, logits=False) == \
        36 * (mamba + mlp) + 4 * (attn + 4 * 2048 + mlp)
    assert flops_hybrid.hybrid_token_flops(cfg, 1057, logits=True) == \
        36 * (mamba + mlp) + 4 * (attn + 4 * 1057 * 2048 + mlp) + head
    # a session: 8 prompts of 1024 (position p attends to p + 1), logits at
    # the prompt's last; 32 decode steps attending to 1025 .. 1056
    per_token = 36 * (mamba + mlp) + 4 * (attn + mlp)
    prefill = 1024 * per_token + 4 * 4 * 2048 * (1024 * 1025 // 2) + head
    decode = 32 * (per_token + head) + 4 * 4 * 2048 * sum(range(1025, 1057))
    assert prefill == 6_228_340_113_408
    assert decode == 208_329_768_960
    assert flops_hybrid.serve_session_flops(cfg, 8, 1024, 32) == 8 * (prefill + decode)
