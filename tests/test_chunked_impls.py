"""§Perf implementations vs their oracles: chunked attention, chunked RWKV-6,
grouped MoE dispatch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.rwkv6_scan.ref import rwkv6_reference
from repro.models import scaled_down
from repro.models.attention import _attention_chunked
from repro.models.moe import moe_apply, moe_params
from repro.models.rwkv6 import rwkv_chunked_bhtd


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("chunk", [64, 128])
def test_chunked_attention_matches_ref(window, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 256, 4, 64)) for kk in ks)
    out = _attention_chunked(q, k, v, window=window, chunk=chunk)
    ref = jnp.swapaxes(
        attention_reference(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                            jnp.swapaxes(v, 1, 2), window=window), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_chunked_rwkv_matches_ref_realistic_decay(chunk):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    b, h, t, d = 2, 3, 256, 32
    r, k, v = (jax.random.normal(x, (b, h, t, d)) * 0.5 for x in ks[:3])
    # the model's decay parameterization: w = exp(-exp(-6 +- sigma))
    w = jnp.exp(-jnp.exp(-6.0 + 0.5 * jax.random.normal(ks[3], (b, h, t, d))))
    u = jax.random.normal(ks[4], (h, d)) * 0.3
    yc = rwkv_chunked_bhtd(r, k, v, w, u, chunk=chunk)
    yr = rwkv6_reference(r, k, v, w, u)
    rel = float(jnp.max(jnp.abs(yc - yr)) / jnp.max(jnp.abs(yr)))
    assert rel < 2e-2, rel


def test_grouped_moe_matches_ungrouped():
    cfg = scaled_down(get_arch("qwen2-moe-a2.7b"))
    hi = dataclasses.replace(cfg.moe, capacity_factor=8.0, dispatch_groups=1)
    grp = dataclasses.replace(cfg.moe, capacity_factor=8.0, dispatch_groups=4)
    p = moe_params(cfg, jax.random.PRNGKey(2), 1)
    p1 = jax.tree.map(lambda x: x[0], p)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 16, cfg.d_model)).astype(jnp.bfloat16)
    y1, _ = moe_apply(p1, x, dataclasses.replace(cfg, moe=hi))
    y2, _ = moe_apply(p1, x, dataclasses.replace(cfg, moe=grp))
    np.testing.assert_allclose(
        np.asarray(y1, np.float32), np.asarray(y2, np.float32), atol=1e-2
    )


def test_chunked_attention_keeps_a_configured_score_scale():
    """Granite's attention (no rotary, scores times 1/64) is the same through
    the chunked path, which scales by head_dim ** -0.5 itself."""
    from repro.models.attention import attention_full, attn_params

    cfg = dataclasses.replace(scaled_down(get_arch("granite-4.0-h-micro")), dtype="float32")
    assert not cfg.rope and cfg.attn_scale == 1 / 64
    p = jax.tree.map(lambda a: a[0], attn_params(cfg, jax.random.PRNGKey(4), 1))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, cfg.d_model))
    positions = jnp.arange(32)
    want = attention_full(p, x, cfg, positions, impl="reference")
    got = attention_full(p, x, cfg, positions, impl="chunked")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
