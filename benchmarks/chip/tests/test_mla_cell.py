"""A whole run of the latent-attention serve cell at a tiny size on the CPU
(the program's scaled-down deepseek-v2-lite, ``tiny_mla.json``), skipping
only the harness's look for a chip: sound, with its control, and with the
latent's path, the served tokens and the flushes broken underneath; and the
FLOP count at the published shapes."""
import json
from pathlib import Path

import pytest

import calibrate
import run

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
CELL = "deepseek-v2-lite.serve.full.32x1024"
SEEDS = (2**31 + 5, 7, 2**32 + 11)
# two flushes a session, as in the cell
TINY = {"prompts": 4, "prompt_len": 12, "decode_steps": 8, "flush_every": 4}
MLA_METRICS = {"prefill_ms_per_session.mla", "decode_ms_per_step.mla",
               "state_persist_ms_per_step.mla", "idle_share.mla", "mfu.mla",
               "host_copy_ms_per_step.mla"}


def _manifest():
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in m["configs"]:
        c["file"] = str((CHIP / "tests" / "tiny_mla.json").relative_to(ROOT))
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
    assert set(hi) == {"logit_gap", "latent_rel_err"}
    assert hi["latent_rel_err"] >= 3 * lo["latent_rel_err"], (lo, hi)
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
        assert set(r["metrics"]) == MLA_METRICS - {"mfu.mla"}
        assert r["metrics"]["state_persist_ms_per_step.mla"]["value"] > 0
        assert r["device"]["busy_s"] > 0
    else:
        assert r["metrics"]["serve_tokens_per_s"]["value"] > 0
        assert r["metrics"]["setup_s"]["unit"] == "s"


def test_latent_persisted_from_the_wrong_step_is_not_correct(limits, monkeypatch):
    """Each flush persists the ``c_kv`` the previous flush took (``k_pe``,
    tokens and step are the flush's own)."""
    from repro.launch import serve

    real = serve._to_host
    last = {}

    def stale(all_tokens, cache=None):
        host = real(all_tokens, cache)
        if cache is not None:
            fresh = {g: host["cache"][g]["pos0"]["c_kv"] for g in host["cache"]
                     if g.startswith("group")}
            for g, c_kv in last.get("c_kv", {}).items():
                host["cache"][g]["pos0"]["c_kv"] = c_kv
            last["c_kv"] = fresh
        return host

    monkeypatch.setattr(serve, "_to_host", stale)
    r = _run(limits)
    assert r["correct"] is False
    assert r["compared"]["latent_rel_err"]["value"] > r["compared"]["latent_rel_err"]["limit"]


def test_an_altered_token_is_not_correct(limits, monkeypatch):
    from repro.launch import serve, steps

    real = steps.make_decode_fn

    def make(cfg):
        step = real(cfg)

        def altered(params, cache, token):
            nxt, new = step(params, cache, token)
            return nxt.at[0, 0].set((nxt[0, 0] + 1) % cfg.vocab), new

        return altered

    monkeypatch.setattr(serve, "make_decode_fn", make)
    r = _run(limits)
    assert r["correct"] is False, r["compared"]


def test_a_dropped_flush_is_not_correct(limits, monkeypatch):
    """The session's last flush writes nothing: the arena holds the step,
    tokens and latent of the one before."""
    from repro.core.manager import EasyCrashManager

    real = EasyCrashManager._flush_now
    last_step = TINY["decode_steps"]

    def dropping(self, step, payload):
        if step != last_step:
            real(self, step, payload)

    monkeypatch.setattr(EasyCrashManager, "_flush_now", dropping)
    r = _run(limits)
    assert r["correct"] is False
    assert r["compared"]["arena_step_off"]["value"] == TINY["flush_every"]


def test_mla_flops_match_a_hand_count():
    import flops_mla

    cfg = json.loads((CHIP / "configs" / "deepseek-v2-lite.json").read_text())
    # an MLA layer, per token: Wq 2048 x 16*192, Wkv_a 2048 x 576, Wkv_b
    # 512 x 16*256, Wo 16*128 x 2048 (multiply-adds, 2 each); then q.k over
    # c positions at 16 x 192 and p.v at 16 x 128
    mla = 2 * (2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048)
    assert mla == 27_525_120
    dense = 3 * 2 * 2048 * 10_944
    # an expert layer: the router over all 64 experts and the shared
    # experts' SwiGLU of 2 x 1408; each routed (token, held expert) pair one
    # SwiGLU of 1408
    moe = 2 * 2048 * 64 + 3 * 2 * 2048 * 2816
    expert = 3 * 2 * 2048 * 1408
    head = 2 * 2048 * 102_400
    assert flops_mla.token_flops(cfg, 1, logits=False) == \
        27 * (mla + 2 * 16 * 320) + dense + 26 * moe
    assert flops_mla.token_flops(cfg, 1057, logits=True) == \
        27 * (mla + 2 * 1057 * 16 * 320) + dense + 26 * moe + head
    # a session: 32 prompts of 1024 (position p attends to p + 1), logits at
    # the prompt's last; 32 decode steps attending to 1025 .. 1056; the
    # routed pairs counted, or expected at 6 x 8 / 64 per token and layer
    per_token = 27 * mla + dense + 26 * moe
    prefill = 1024 * per_token + 27 * 2 * 16 * 320 * (1024 * 1025 // 2) + head
    decode = 32 * (per_token + head) + 27 * 2 * 16 * 320 * sum(range(1025, 1057))
    assert flops_mla.serve_session_flops(cfg, 32, 1024, 32, expert_pairs=700_000) == \
        32 * (prefill + decode) + 700_000 * expert
    assert flops_mla.expected_pairs_per_token(cfg) == 0.75
    assert flops_mla.serve_session_flops(cfg, 32, 1024, 32) == \
        32 * (prefill + decode) + 32 * 1056 * 26 * 0.75 * expert
