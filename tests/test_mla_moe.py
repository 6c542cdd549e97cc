"""Latent attention (MLA) and a share of routed experts (deepseek-v2-lite,
scaled down to width 64: one dense layer and two expert layers, 8 routed
experts in shares of 2) against the benchmark's plain float32 reference
(``benchmarks/chip/references/mla_moe_decoder.py``), on seeded random
weights: the weight draw, prefill then absorbed decoding through the latent
cache against the reference's full forward pass, the persisted latent, the
expert shares against the whole layer, a dispatch that drops nothing, and
YaRN and the softmax scale at the published values."""
import dataclasses
import json
import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.serve import _splice_cache
from repro.models import decode_step, init_cache, init_params, prefill, scaled_down
from repro.models import moe as moe_mod
from repro.models.attention import mla_inv_freq, mla_score_scale
from repro.models.moe import moe_apply, moe_params

ROOT = Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"
SEED = 2**31 + 7
ROWS, PROMPT, DECODE = 3, 13, 9


@pytest.fixture(scope="module")
def ref():
    import sys

    sys.path.insert(0, str(CHIP))
    from modules import load_module

    return load_module(CHIP / "references" / "mla_moe_decoder.py", "bench_ref_mla_moe_decoder")


@pytest.fixture(scope="module")
def model(ref):
    cfg_file = json.loads((CHIP / "tests" / "tiny_mla.json").read_text())
    cfg_file["num_hidden_layers"] = 3
    dims = ref.Dims.from_config(cfg_file)
    cfg = scaled_down(get_arch("deepseek-v2-lite"), layers=3, width=cfg_file["hidden_size"])
    assert [cfg.group_uses_moe(g) for g in range(len(cfg.groups))] == [False, True]
    assert cfg.moe.num_experts == 8 and cfg.moe.experts_held == 2 and dims.held == 2
    return cfg, dims, init_params(cfg, jax.random.PRNGKey(SEED)), ref.init_weights(dims, SEED)


def test_reference_draws_the_programs_weights(model):
    _, _, params, w = model
    pairs = [(params[k], w[k]) for k in ("embed", "unembed", "final_norm")]
    names = {"router": "router", "w_gate": "e_gate", "w_up": "e_up", "w_down": "e_down"}
    for gi, lw in enumerate(w["groups"]):
        p = params[f"group{gi}"]["pos0"]
        pairs += [(p[k], lw[k]) for k in ("norm1", "norm2")]
        pairs += [(v, lw[k]) for k, v in p["attn"].items()]
        if gi == 0:
            pairs += [(v, lw[k]) for k, v in p["mlp"].items()]
        else:
            pairs += [(p["moe"][k], lw[v]) for k, v in names.items()]
            pairs += [(v, lw["s_" + k[2:]]) for k, v in p["moe"]["shared"].items()]
    assert len(pairs) == 3 + 10 + 14
    for a, b in pairs:
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))


def _serve(cfg, params, tokens):
    """Prefill the prompt, then decode through the cache, as the server
    does: logits at every position from the prompt's last, and the cache
    after the prefill and after the decode steps."""
    logits, cache = jax.jit(partial(prefill, cfg))(params, jnp.asarray(tokens[:, :PROMPT]))
    out = [np.asarray(logits, np.float32)]
    after_prefill = cache
    cache = _splice_cache(cfg, init_cache(cfg, ROWS, PROMPT + DECODE), cache, PROMPT)
    step = jax.jit(partial(decode_step, cfg))
    for i in range(PROMPT, PROMPT + DECODE - 1):
        logits, cache = step(params, jnp.asarray(tokens[:, i:i + 1]), cache)
        out.append(np.asarray(logits[:, 0], np.float32))
    return np.stack(out, axis=1), after_prefill, cache


def _tokens(cfg):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(1), (ROWS, PROMPT + DECODE),
                                         0, cfg.vocab))


def _as(cfg, params, dtype):
    return (dataclasses.replace(cfg, dtype=dtype),
            jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16 else a, params))


# Tolerances. "float32": the program computed in float32 on the bf16
# weights; it differs from the reference only in summation order (absorbed
# decode against expanded attention, tiles of routed tokens against every
# held expert on every token), so 1e-5 of the logits' scale and relative
# latent errors of 1e-5 hold with room. "bfloat16": the program as served,
# activations and the latent rounded to bf16 (2^-8 relative) at every
# layer; errors compound over the 3 layers to about 1% of the logits' scale
# and 0.3-0.7% on the latent; 0.05 and 0.03 leave room and are still below
# what one float8 step gives (the control's readings, benchmarks/chip/tests).
TOL = {"float32": (1e-5, 1e-5), "bfloat16": (0.05, 0.03)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_match_the_reference(model, ref, dtype):
    cfg, dims, params, w = model
    cfg, params = _as(cfg, params, dtype)
    tokens = _tokens(cfg)
    got, _, _ = _serve(cfg, params, tokens)
    want, _ = ref.forward(dims, w, tokens[:, :-1], PROMPT - 1, DECODE)
    want = np.asarray(want)
    assert got.shape == want.shape == (ROWS, DECODE, cfg.vocab)
    logit_tol, _ = TOL[dtype]
    assert np.abs(got - want).max() <= logit_tol * np.abs(want).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_cache_matches_the_reference(model, ref, dtype):
    """The cache holds each layer's normed latent and rotated key, and no
    per-head K/V: after the prefill and after the decode steps, against
    the reference's at the same positions."""
    cfg, dims, params, w = model
    cfg, params = _as(cfg, params, dtype)
    tokens = _tokens(cfg)
    _, after_prefill, after_decode = _serve(cfg, params, tokens)
    _, tol = TOL[dtype]
    for cache, n in ((after_prefill, PROMPT), (after_decode, PROMPT + DECODE - 1)):
        _, want = ref.forward(dims, w, tokens[:, :n], n - 1, 1)
        for gi in range(2):
            got = cache[f"group{gi}"]["pos0"]
            assert set(got) == {"c_kv", "k_pe"}
            for leaf in ("c_kv", "k_pe"):
                g = np.asarray(got[leaf], np.float32)[:, :, :n]
                err = float(np.asarray(ref.rel_err(g, want[gi][leaf])).max())
                assert err <= tol, (n, gi, leaf, err)
    assert after_decode["group1"]["pos0"]["c_kv"].dtype == jnp.dtype(dtype)


def _reference_pairs(ref, dims, w, tokens):
    """(token, held expert) pairs the reference's routing chooses in every
    expert layer, for the positions of ``tokens``."""
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    pairs = 0
    for gi, gw in enumerate(w["groups"]):
        for li in range(gw["norm1"].shape[0]):
            lw = jax.tree.map(lambda a: a[li], gw)
            y, _ = ref._attention(dims, "f32", ref._rms(x, lw["norm1"], dims.norm_eps), lw)
            x = x + y
            h = ref._rms(x, lw["norm2"], dims.norm_eps)
            if gi == 0:
                x = x + ref._swiglu("f32", h, lw["w_gate"], lw["w_up"], lw["w_down"])
            else:
                pairs += int(jnp.sum(ref.route(dims, h, lw["router"]) > 0))
                x = x + ref._experts(dims, "f32", h, lw)
    return pairs


def test_served_counters_count_the_routed_pairs(model, ref):
    """The cache's ``moe`` counters: the (token, held expert) pairs the
    prefill and the decode steps routed here, as many as the reference's
    routing chooses over the same positions, and none dropped."""
    cfg, dims, params, w = model
    cfg, params = _as(cfg, params, "float32")
    tokens = _tokens(cfg)
    _, after_prefill, after_decode = _serve(cfg, params, tokens)
    assert int(after_prefill["moe"]["dropped"]) == int(after_decode["moe"]["dropped"]) == 0
    assert int(after_prefill["moe"]["pairs"]) == _reference_pairs(ref, dims, w, tokens[:, :PROMPT])
    n = PROMPT + DECODE - 1
    assert int(after_decode["moe"]["pairs"]) == _reference_pairs(ref, dims, w, tokens[:, :n])


def _layer(cfg, rank: int, ep: int, impl: str):
    moe = dataclasses.replace(cfg.moe, ep_size=ep, ep_rank=rank, impl=impl)
    c = dataclasses.replace(cfg, moe=moe, dtype="float32")
    p = jax.tree.map(lambda a: a[0], moe_params(c, jax.random.PRNGKey(5), 1))
    return c, p


@pytest.mark.parametrize("impl", ["dropless", "dense"])
def test_expert_shares_add_up_to_the_whole_layer(model, impl):
    """Every share's part of the layer, the shared experts counted once,
    adds up to the layer with all 8 experts held: the held experts are the
    whole layer's, and the router is the same."""
    cfg, *_ = model
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 11, cfg.d_model), jnp.float32)
    whole_cfg, whole_p = _layer(cfg, 0, 1, impl)
    whole, whole_stats = moe_apply(whole_p, x, whole_cfg)
    parts, pairs = [], 0
    for rank in range(4):
        c, p = _layer(cfg, rank, 4, impl)
        assert p["w_gate"].shape[0] == 2
        np.testing.assert_array_equal(p["w_gate"], whole_p["w_gate"][2 * rank:2 * rank + 2])
        y, stats = moe_apply(p, x, c)
        parts.append(np.asarray(y))
        pairs += int(stats["pairs"])
        assert int(stats["dropped"]) == 0
    shared = np.asarray(moe_mod.mlp_apply(whole_p["shared"], x, whole_cfg))
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(total, np.asarray(whole), rtol=1e-5,
                               atol=1e-5 * np.abs(np.asarray(whole)).max())
    assert pairs == int(whole_stats["pairs"]) == 3 * 11 * cfg.moe.top_k


@pytest.mark.parametrize("tile", [8, 512])
def test_router_forced_onto_one_expert_drops_no_token(model, monkeypatch, tile):
    """A router that sends every token to held expert 0: the dropless
    dispatch computes all of them (over many tiles where tiles are small)
    and matches every held expert on every token; capacity buffers drop."""
    cfg, *_ = model
    monkeypatch.setattr(moe_mod, "TILE", tile)
    c, p = _layer(cfg, 0, 4, "dropless")
    p["router"] = p["router"].at[:, 0].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (4, 25, cfg.d_model), jnp.float32))
    y, stats = moe_apply(p, x, c)
    dense_c = dataclasses.replace(c, moe=dataclasses.replace(c.moe, impl="dense"))
    want, _ = moe_apply(p, x, dense_c)
    assert int(stats["dropped"]) == 0 and int(stats["pairs"]) >= 100
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5 * np.abs(np.asarray(want)).max())
    sort_c = dataclasses.replace(c, moe=dataclasses.replace(c.moe, impl="sort", ep_size=1,
                                                            ep_rank=0))
    sort_p = jax.tree.map(lambda a: a[0], moe_params(sort_c, jax.random.PRNGKey(5), 1))
    sort_p["router"] = sort_p["router"].at[:, 0].set(10.0)
    _, sort_stats = moe_apply(sort_p, x, sort_c)
    assert int(sort_stats["dropped"]) > 0


def test_yarn_and_softmax_scale_at_the_published_values():
    """YaRN over the 64 rope dimensions: the correction range 10.47 -> 10
    and 22.51 -> 23, the original frequencies below pair 10, those divided
    by 40 from pair 23, a linear ramp between; DeepSeek-V2's softmax scale
    192^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2."""
    cfg = get_arch("deepseek-v2-lite")
    base = 1.0 / 10_000 ** (np.arange(0, 64, 2) / 64)
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10_000))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10_000))
    assert (round(low, 2), round(high, 2)) == (10.47, 22.51)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base * (1 - ramp) + base / 40 * ramp
    got = np.asarray(mla_inv_freq(cfg))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert round(mscale, 5) == 1.26080
    assert mla_score_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2, rel=1e-12)
    assert round(mla_score_scale(cfg), 6) == 0.114721


def test_the_published_configuration_holds_one_chips_share():
    cfg = get_arch("deepseek-v2-lite")
    m, a = cfg.moe, cfg.mla
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == \
        (27, 2048, 16, 10_944, 102_400)
    assert (a.kv_lora_rank, a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim) == \
        (512, 128, 64, 128)
    assert (m.num_experts, m.top_k, m.d_ff_expert, m.d_ff_shared) == (64, 6, 1408, 2816)
    assert (m.experts_held, m.first_held, m.first_k_dense) == (8, 0, 1)
    assert not m.norm_topk_prob and m.impl == "dropless"
    assert cfg.groups == ((("attn",), 1), (("attn",), 26)) and not cfg.tie_embeddings


def test_a_traced_session_carries_the_latent_and_the_experts(tmp_path):
    """``serve.session`` counts the latent's bytes apart from K/V and
    recurrent state, and the (token, held expert) pairs the session routed;
    ``serve.prefill`` the pairs it dropped: none."""
    import glob

    from jax.profiler import ProfileData

    from repro.launch import serve

    args = ["--arch", "deepseek-v2-lite", "--width", "64", "--prompts", "2",
            "--prompt-len", "10", "--decode-steps", "4", "--flush-every", "2",
            "--persist-mode", "full"]
    jax.profiler.start_trace(str(tmp_path / "trace"))
    stats = serve.main([*args, "--workdir", str(tmp_path / "served")])
    jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    spans = [(ev.name, dict(ev.stats)) for plane in ProfileData.from_file(pb).planes
             if plane.name.startswith("/host:") for line in plane.lines for ev in line.events]
    (session,) = [s for name, s in spans if name == "serve.session"]
    (pre,) = [s for name, s in spans if name == "serve.prefill"]
    cfg = scaled_down(get_arch("deepseek-v2-lite"), width=64)
    a = cfg.mla
    latent = cfg.n_layers * 2 * (10 + 4 + 1) * (a.kv_lora_rank + a.qk_rope_head_dim) * 2
    assert (session["latent_bytes"], session["kv_bytes"], session["state_bytes"]) == (latent, 0, 0)
    assert session["experts_held"] == stats["experts_held"] == 2
    assert session["expert_pairs"] == stats["expert_pairs"] > 0
    assert pre["dropped"] == 0
    # full mode: every object written whole, the latent among them
    whole = {s["object"] for name, s in spans if name == "flush.whole"}
    assert {f"cache/group{g}/pos0/{leaf}" for g in (0, 1) for leaf in ("c_kv", "k_pe")} <= whole
