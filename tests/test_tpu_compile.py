"""Ahead-of-time compiles for one TPU v5e chip, without the chip.

The TPU compiler is installed wherever jax's TPU support is, and compiles
for a described (not attached) chip.  These catch what interpret mode never
sees: block shapes off the (8, 128) tiling, a rank-1 block that is not a
multiple of 128, bool reductions Mosaic cannot lay out.  Every compile runs
in the test's own process; the topology is described only inside a fixture,
so collecting this file never loads the TPU library.

The kernels' public ops choose interpret mode from ``jax.default_backend()``,
which is the CPU here; each test steers that choice to the chip's with
``monkeypatch`` and compiles a fresh ``jax.jit`` of the op's body.
"""
import functools
import importlib.util

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.delta_snapshot import ops as delta_ops
from repro.kernels.flash_attention import ops as flash_ops

#: the persisting server's cache at stablelm-1.6b width: one K (or V) leaf of
#: (layers 24, batch 4, seq 65, heads 32, head_dim 64) bfloat16, in bytes
SERVE_CACHE_BYTES = 24 * 4 * 65 * 32 * 64 * 2


@pytest.fixture(scope="module")
def topo():
    """The TPU library takes a lock on load that any other process holding
    it would refuse; a described topology opens no chip, so this process
    may load it alongside others (test workers compile side by side)."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) in this install")
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        mp.setenv("TPU_LOG_DIR", "disabled")
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """Compiles for a described chip cannot be read back from the persistent
    cache without that chip; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("nbytes", [
    pytest.param(SERVE_CACHE_BYTES, id="serve-cache"),
    pytest.param(64 * 131_073 - 5, id="odd-blocks"),
])
def test_dirty_block_mask_compiles_for_v5e(nbytes, one_chip, no_compile_cache, monkeypatch):
    monkeypatch.setattr(delta_ops, "_on_tpu", lambda: True)
    fn = jax.jit(functools.partial(delta_ops.dirty_block_mask.__wrapped__, block_elems=64))
    buf = jax.ShapeDtypeStruct((nbytes,), jnp.uint8, sharding=one_chip)
    hlo = fn.lower(buf, buf).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_for_v5e(one_chip, no_compile_cache, monkeypatch):
    """stablelm-1.6b heads: batch 4, 32 heads x 64, sequence 1024, bf16."""
    monkeypatch.setattr(flash_ops, "_on_tpu", lambda: True)
    fn = jax.jit(functools.partial(flash_ops.flash_attention.__wrapped__, causal=True))
    qkv = jax.ShapeDtypeStruct((4, 1024, 32, 64), jnp.bfloat16, sharding=one_chip)
    hlo = fn.lower(qkv, qkv, qkv).compile().as_text()
    assert "tpu_custom_call" in hlo
