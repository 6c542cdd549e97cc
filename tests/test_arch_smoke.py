"""Per-architecture smoke tests: reduced config, one forward + train step on
CPU, asserting output shapes and no NaNs.  Full configs are exercised only by
the dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, arch_names, get_arch
from repro.launch.steps import init_train_state, make_train_step
from repro.models import (
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_and_aux,
    scaled_down,
)

ALL = sorted(ARCHS)


def _batch(cfg, key, b=2, s=16):
    batch = {"tokens": jax.random.randint(key, (b, s + 1), 0, cfg.vocab)}
    if cfg.frontend_tokens:
        batch["patches"] = jax.random.normal(
            key, (b, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16
        )
    return batch


@pytest.mark.parametrize("name", ALL)
def test_forward_shapes_and_finite(name):
    cfg = scaled_down(get_arch(name))
    params = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    patches = None
    if cfg.frontend_tokens:
        patches = jax.random.normal(
            jax.random.PRNGKey(2), (b, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16
        )
    logits, aux = forward(cfg, params, tokens, patches)
    s_total = s + cfg.frontend_tokens
    assert logits.shape == (b, s_total, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


@pytest.mark.parametrize("name", ALL)
def test_train_step_no_nans(name):
    cfg = scaled_down(get_arch(name))
    step = make_train_step(cfg, peak_lr=1e-3, total_steps=10)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(3))
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert int(new_state["step"]) == 1
    # parameters actually moved
    before = jax.tree.leaves(state["params"])[1]
    after = jax.tree.leaves(new_state["params"])[1]
    assert not np.array_equal(np.asarray(before), np.asarray(after))
    for leaf in jax.tree.leaves(new_state["params"]):
        assert np.all(np.isfinite(np.asarray(leaf, np.float32)))


@pytest.mark.parametrize("name", ALL)
def test_decode_step_shapes(name):
    cfg = scaled_down(get_arch(name))
    params = init_params(cfg, jax.random.PRNGKey(0))
    b = 2
    cache = init_cache(cfg, b, max_len=32)
    token = jax.random.randint(jax.random.PRNGKey(4), (b, 1), 0, cfg.vocab)
    logits, new_cache = decode_step(cfg, params, token, cache)
    assert logits.shape == (b, 1, cfg.vocab)
    assert int(new_cache["t"]) == 1
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


def test_all_archs_registered():
    assert len(ALL) == 12
    assert set(ALL) == {
        "musicgen-medium", "minitron-8b", "granite-8b", "stablelm-1.6b",
        "nemotron-4-340b", "recurrentgemma-9b", "rwkv6-3b",
        "llama4-scout-17b-a16e", "qwen2-moe-a2.7b", "internvl2-76b",
        "granite-4.0-h-micro", "deepseek-v2-lite",
    }


def test_exact_assigned_configs():
    """The full configs carry the exact assigned hyper-parameters."""
    expect = {
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "granite-8b": (36, 4096, 32, 8, 14336, 49152),
        "stablelm-1.6b": (24, 2048, 32, 32, 5632, 100352),
        "nemotron-4-340b": (96, 18432, 96, 8, 73728, 256000),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }
    for name, (L, d, hq, hkv, ff, V) in expect.items():
        cfg = get_arch(name)
        assert cfg.n_layers == L, name
        assert cfg.d_model == d, name
        assert cfg.n_heads == hq, name
        assert cfg.n_kv_heads == hkv, name
        assert cfg.d_ff == ff, name
        assert cfg.vocab == V, name
    q = get_arch("qwen2-moe-a2.7b").moe
    assert q.num_experts == 60 and q.top_k == 4 and q.d_ff_shared == 5632
    l4 = get_arch("llama4-scout-17b-a16e").moe
    assert l4.num_experts == 16 and l4.top_k == 1
    rg = get_arch("recurrentgemma-9b")
    assert rg.total_layers() == 38 and rg.attn_window == 2048
    gr = get_arch("granite-4.0-h-micro")
    kinds = [k for pattern, rep in gr.groups for _ in range(rep) for k in pattern]
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    assert (gr.mamba.d_state, gr.mamba.head_dim, gr.mamba.expand, gr.mamba.chunk) == (128, 64, 2, 256)
    assert not gr.rope and gr.attn_scale == 1 / 64 and gr.tie_embeddings
    assert (gr.embedding_multiplier, gr.residual_multiplier, gr.logits_scaling) == (12, 0.22, 8)
