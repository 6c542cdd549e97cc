"""What the decode server leaves in its arena: the device's decode state at
the last flush, bit for bit, whichever steps brought it to the host; and a
server killed between two flushes resumes at the last one and serves the
tokens of an uninterrupted session."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import NVMArena
from repro.core.manager import flatten_state
from repro.launch import serve
from repro.launch.steps import make_decode_fn, make_prefill_step
from repro.models import init_cache, init_params, scaled_down

WIDTH, PROMPTS, PROMPT_LEN, STEPS, EVERY, SEED = 64, 2, 8, 10, 4, 3
LAST_FLUSH = STEPS // EVERY * EVERY


def _serve(workdir, mode="delta", *extra):
    return serve.main(["--width", str(WIDTH), "--prompts", str(PROMPTS),
                       "--prompt-len", str(PROMPT_LEN), "--decode-steps", str(STEPS),
                       "--flush-every", str(EVERY), "--persist-mode", mode,
                       "--seed", str(SEED), "--workdir", str(workdir), *extra])


@pytest.fixture(scope="module")
def device_state():
    """The cache and the token buffer after ``LAST_FLUSH`` decode steps,
    computed here with the server's prefill and decode steps."""
    cfg = scaled_down(get_arch("stablelm-1.6b"), width=WIDTH)
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    prompts = jax.random.randint(jax.random.PRNGKey(7), (PROMPTS, PROMPT_LEN), 0, cfg.vocab)
    logits, cache = jax.jit(make_prefill_step(cfg))(params, {"tokens": prompts})
    cache = serve._splice_cache(cfg, init_cache(cfg, PROMPTS, PROMPT_LEN + STEPS + 1),
                                cache, PROMPT_LEN)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    tokens = [prompts, token]
    decode = jax.jit(make_decode_fn(cfg))
    for _ in range(LAST_FLUSH):
        token, cache = decode(params, cache, token)
        tokens.append(token)
    state = {"cache": cache, "tokens": jnp.concatenate(tokens, axis=1),
             "__step__": np.asarray(LAST_FLUSH, np.int64)}
    return {k: np.asarray(v) for k, v in flatten_state(state).items()}


@pytest.mark.parametrize("mode", ["delta", "full"])
def test_the_arena_holds_the_device_state_of_the_last_flush(tmp_path, device_state, mode):
    _serve(tmp_path, mode)
    arena = NVMArena.reattach(str(tmp_path / "serve_arena"))
    assert sorted(arena.names()) == sorted(device_state)
    for name, want in device_state.items():
        got = arena.get(name)
        assert (got.dtype, got.shape) == (want.dtype, want.shape), name
        assert got.tobytes() == want.tobytes(), name


def test_a_failure_between_flushes_resumes_at_the_last_flush(tmp_path, capsys):
    whole = _serve(tmp_path / "whole")
    resumed = _serve(tmp_path / "crashed", "delta", "--inject-failure-at", str(EVERY + 2))
    out = capsys.readouterr().out
    assert f"injected failure at decode step {EVERY + 2}" in out
    assert f"resuming decode at step {EVERY}" in out
    assert resumed["resumed"] and resumed["decode_steps"] == STEPS - EVERY
    assert np.array_equal(resumed["tokens"], whole["tokens"])
    assert resumed["tokens"].shape == (PROMPTS, PROMPT_LEN + 1 + STEPS)
