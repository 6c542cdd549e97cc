"""The Mamba-2 + attention hybrid (granite-4.0-h-micro, scaled down to one
10-layer period, chunk 8) against the benchmark's plain float32 reference
(``benchmarks/chip/references/hybrid_decoder.py``), on seeded random
weights: the weight draw, prefill then decoding through the cache against
the reference's full forward pass, the decode state, and the chunked SSD
against the sequential recurrence."""
import dataclasses
import json
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.serve import _splice_cache
from repro.models import decode_step, init_cache, init_params, prefill, scaled_down
from repro.models.mamba2 import ssd_chunked

ROOT = Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"
SEED = 2**31 + 3
ROWS, PROMPT, DECODE = 3, 13, 14   # the prompt is not a multiple of the chunk


@pytest.fixture(scope="module")
def ref():
    import sys

    sys.path.insert(0, str(CHIP))
    from modules import load_module

    return load_module(CHIP / "references" / "hybrid_decoder.py", "bench_ref_hybrid_decoder")


@pytest.fixture(scope="module")
def model(ref):
    cfg_file = json.loads((CHIP / "tests" / "tiny_hybrid.json").read_text())
    dims = ref.Dims.from_config(cfg_file)
    cfg = scaled_down(get_arch("granite-4.0-h-micro"), width=cfg_file["hidden_size"])
    assert cfg.n_layers == 10 and cfg.mamba.chunk == 8
    return cfg, dims, init_params(cfg, jax.random.PRNGKey(SEED)), ref.init_weights(dims, SEED)


def test_reference_draws_the_programs_weights(model):
    cfg, dims, params, w = model
    assert "unembed" not in params  # tied
    pairs = [(params["embed"], w["embed"]), (params["final_norm"], w["final_norm"])]
    for pi, kind in enumerate(dims.period):
        p, lw = params["group0"][f"pos{pi}"], w["layers"][pi]
        mixer = p["attn"] if kind == "attention" else p["mamba"]
        pairs += [(p[k], lw[k]) for k in ("norm1", "norm2")]
        pairs += [(p["mlp"][k], lw[k]) for k in ("w_gate", "w_up", "w_down")]
        pairs += [(v, lw[k]) for k, v in mixer.items()]
        assert set(lw) == {"norm1", "norm2", "w_gate", "w_up", "w_down", *mixer}
    for a, b in pairs:
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))


def test_decay_and_step_are_drawn_as_mamba2_draws_them(model):
    _, _, params, _ = model
    m = params["group0"]["pos0"]["mamba"]
    a = np.exp(np.asarray(m["A_log"]))
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))  # softplus undoes the inverse
    assert a.min() >= 1.0 and a.max() <= 16.0
    assert dt.min() >= 1e-3 * (1 - 1e-5) and dt.max() <= 0.1 * (1 + 1e-5)
    assert m["A_log"].dtype == m["dt_bias"].dtype == jnp.float32


def _serve(cfg, params, tokens):
    """Prefill the prompt, then decode through the cache, as the server
    does: logits at every position from the prompt's last, and the cache."""
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


# Tolerances. "float32": the program computed in float32 on the bf16
# weights; it differs from the reference only in summation order (chunked
# SSD against the step-by-step recurrence, batched against whole-sequence
# matmuls), so 1e-5 of the logits' scale and relative state errors of 1e-5
# hold with room (observed about 1e-6). "bfloat16": the program as served,
# activations and K/V rounded to bf16 (2^-8 relative) at every layer;
# errors compound over the 10 layers, observed about 2% of the logits' scale
# and 1-3% on the state; 0.08 and 0.06 leave room and are still below what
# one float8 step gives (the control's 14-30%, benchmarks/chip/tests).
TOL = {"float32": (1e-5, 1e-5), "bfloat16": (0.08, 0.06)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_match_the_reference(model, ref, dtype):
    cfg, dims, params, w = model
    cfg = dataclasses.replace(cfg, dtype=dtype)
    params = jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16 else a, params)
    tokens = _tokens(cfg)
    got, _, _ = _serve(cfg, params, tokens)
    want, _ = ref.forward(dims, w, tokens[:, :-1], PROMPT - 1, DECODE)
    want = np.asarray(want)
    assert got.shape == want.shape == (ROWS, DECODE, cfg.vocab)
    logit_tol, _ = TOL[dtype]
    assert np.abs(got - want).max() <= logit_tol * np.abs(want).max()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decode_state_matches_the_reference(model, ref, dtype):
    """SSM states, conv windows and K/V, after the prefill and after the
    decode steps, against the reference's after the same positions."""
    cfg, dims, params, w = model
    cfg = dataclasses.replace(cfg, dtype=dtype)
    params = jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16 else a, params)
    tokens = _tokens(cfg)
    _, after_prefill, after_decode = _serve(cfg, params, tokens)
    _, state_tol = TOL[dtype]
    for cache, n in ((after_prefill, PROMPT), (after_decode, PROMPT + DECODE - 1)):
        _, want = ref.forward(dims, w, tokens[:, :n], n - 1, 1)
        for pi, kind in enumerate(dims.period):
            got = cache["group0"][f"pos{pi}"]
            leaves = ("k", "v") if kind == "attention" else ("ssm", "conv")
            assert set(got) == set(leaves)
            for leaf in leaves:
                g = np.asarray(got[leaf], np.float32)[:, :, :n] if leaf in ("k", "v") else got[leaf]
                err = float(np.asarray(ref.rel_err(g, want[pi][leaf])).max())
                assert err <= state_tol, (n, pi, leaf, err)
        assert all(cache["group0"][f"pos{pi}"]["ssm"].dtype == jnp.float32
                   for pi, kind in enumerate(dims.period) if kind == "mamba")


def _sequential(x, dt, a, b, c):
    """The recurrence one step at a time, in float64: S = exp(dt a) S +
    dt x b^T, y = S c."""
    x, dt, b, c = (np.asarray(t, np.float64) for t in (x, dt, b, c))
    bsz, s, h, p = x.shape
    heads_per_group = h // b.shape[2]
    state = np.zeros((bsz, h, p, b.shape[-1]))
    ys = []
    for t in range(s):
        bt = np.repeat(b[:, t], heads_per_group, axis=1)
        ct = np.repeat(c[:, t], heads_per_group, axis=1)
        state = (np.exp(dt[:, t] * np.asarray(a, np.float64))[..., None, None] * state
                 + (dt[:, t, :, None] * x[:, t])[..., None] * bt[:, :, None, :])
        ys.append(np.einsum("bhpn,bhn->bhp", state, ct))
    return np.stack(ys, axis=1), state


@pytest.mark.parametrize("length", [1, 5, 8, 13, 16, 24])
@pytest.mark.parametrize("groups", [1, 2])
def test_chunked_ssd_matches_the_sequential_recurrence(length, groups):
    """Lengths that are and are not multiples of the chunk (8); float32
    against float64, so 1e-5 relative covers the chunked algorithm's other
    summation order."""
    keys = jax.random.split(jax.random.PRNGKey(length * 10 + groups), 5)
    bsz, h, p, n = 2, 4, 3, 5
    x = jax.random.normal(keys[0], (bsz, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (bsz, length, h)) - 2.0)
    a = -jax.random.uniform(keys[2], (h,), minval=1.0, maxval=16.0)
    b = jax.random.normal(keys[3], (bsz, length, groups, n))
    c = jax.random.normal(keys[4], (bsz, length, groups, n))
    y, final = jax.jit(partial(ssd_chunked, chunk=8))(x, dt, a, b, c)
    y_seq, final_seq = _sequential(x, dt, a, b, c)
    assert y.shape == (bsz, length, h, p) and final.shape == (bsz, h, p, n)
    np.testing.assert_allclose(np.asarray(y), y_seq, rtol=1e-5, atol=1e-5 * np.abs(y_seq).max())
    np.testing.assert_allclose(np.asarray(final), final_seq, rtol=1e-5,
                               atol=1e-5 * np.abs(final_seq).max())
