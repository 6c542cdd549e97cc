"""Differential tests: Pallas kernels vs their pure references, CPU interpret.

Unlike ``test_kernels.py`` (hypothesis-driven sweeps), these are plain
parametrized tests so they run wherever a Pallas-capable jax exists — the
dtype x odd-shape grid is the point: non-multiple-of-block sizes exercise
the padding/tiling edges of ``delta_snapshot`` and the tail-chunk handling
of ``rwkv6_scan``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "jax.experimental.pallas", reason="kernel tests need a Pallas-capable jax build"
)

from repro.core.arena import NVMArena
from repro.core.blocks import block_diff_mask
from repro.core.delta_persist import delta_block_mask
from repro.core.manager import EasyCrashManager, FlushPolicy
from repro.kernels.delta_snapshot.ops import dirty_block_mask
from repro.kernels.delta_snapshot.ref import dirty_block_mask_reference
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.rwkv6_scan.ref import rwkv6_reference

pytestmark = pytest.mark.kernel


# ------------------------------------------------------------- delta snapshot
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("n", [1, 7, 255, 256, 257, 1000, 4097])
def test_dirty_block_mask_differential(n, dtype):
    """Kernel == jnp oracle for every dtype at odd (non-multiple-of-block)
    lengths; the zero-padding of the tail block must never read as dirty."""
    be = 256
    rng = np.random.default_rng(n)
    if dtype == jnp.int32:
        x = rng.integers(-1000, 1000, size=n).astype(np.int32)
    else:
        x = rng.standard_normal(n).astype(np.float32)
    p = x.copy()
    idx = rng.choice(n, size=min(5, n), replace=False)
    p[idx] += 1
    xj = jnp.asarray(x, dtype)
    pj = jnp.asarray(p, dtype)
    got = np.asarray(dirty_block_mask(xj, pj, block_elems=be))
    nb = -(-n // be)
    assert got.shape == (nb,) and got.dtype == np.int32
    xpad = jnp.zeros(nb * be, dtype).at[:n].set(xj)
    ppad = jnp.zeros(nb * be, dtype).at[:n].set(pj)
    ref = np.asarray(
        dirty_block_mask_reference(xpad.reshape(nb, be), ppad.reshape(nb, be))
    )
    np.testing.assert_array_equal(got, ref)
    changed = np.flatnonzero(np.asarray(xj) != np.asarray(pj))
    assert set(np.flatnonzero(got)) == set(changed // be)
    # identical inputs: padding contributes no phantom dirt
    clean = np.asarray(dirty_block_mask(xj, xj, block_elems=be))
    assert not clean.any()


@pytest.mark.parametrize("n,block_bytes", [(300, 64), (1024, 64), (65, 32)])
def test_dirty_block_mask_agrees_with_cpu_block_diff(n, block_bytes):
    """The TPU flush-block mask and the campaign engine's byte-level
    block_diff_mask must flag the same blocks when block sizes align
    (block_elems * itemsize == block_bytes)."""
    elems = block_bytes // 4
    rng = np.random.default_rng(7)
    x = rng.standard_normal(n).astype(np.float32)
    p = x.copy()
    p[rng.choice(n, size=4, replace=False)] *= -1.0
    kernel_mask = np.asarray(
        dirty_block_mask(jnp.asarray(x), jnp.asarray(p), block_elems=elems)
    ).astype(bool)
    cpu_mask = block_diff_mask(x, p, block_bytes=block_bytes)
    np.testing.assert_array_equal(kernel_mask, cpu_mask)


@pytest.mark.parametrize("dtype,block_elems,n", [
    pytest.param(np.uint8, 64, 64 * 64, id="u8-one-tile-even"),
    pytest.param(np.uint8, 64, 64 * 129 - 5, id="u8-odd-blocks-partial-tail"),
    pytest.param(np.uint8, 64, 64 * 16384 + 64 * 9, id="u8-two-tiles"),
    pytest.param(np.uint8, 7, 1000, id="u8-unpacked-block"),
    pytest.param(np.uint8, 12, 999, id="u8-3-word-block"),
    pytest.param("bfloat16", 64, 6001, id="bf16-packed"),
    pytest.param(np.float32, 24, 3001, id="f32-24-word-block"),
    pytest.param(np.float32, 1024, 5000, id="f32-block-spans-lanes"),
    pytest.param(np.float32, 1, 301, id="f32-one-word-block"),
])
def test_dirty_block_mask_tiling(dtype, block_elems, n):
    """The lane-dense word layout against the byte-level block_diff_mask:
    packed and zero-extended blocks, blocks narrower and wider than a
    128-lane row, odd block counts, partial tail blocks, several tiles."""
    dtype = jnp.bfloat16.dtype if dtype == "bfloat16" else np.dtype(dtype)
    rng = np.random.default_rng(n)
    x = rng.integers(0, 256, n * dtype.itemsize, dtype=np.uint8).view(dtype)
    p = x.copy().view(np.uint8)
    edits = rng.choice(p.size, size=min(11, p.size), replace=False)
    p[edits] ^= rng.integers(1, 256, edits.size, dtype=np.uint8)
    p = p.view(dtype)
    got = np.asarray(dirty_block_mask(jnp.asarray(x), jnp.asarray(p), block_elems=block_elems))
    want = block_diff_mask(x, p, block_bytes=block_elems * dtype.itemsize)
    np.testing.assert_array_equal(got.astype(bool), want)
    assert got.dtype == np.int32 and want.any()
    clean = np.asarray(dirty_block_mask(jnp.asarray(x), jnp.asarray(x), block_elems=block_elems))
    assert clean.shape == want.shape and not clean.any()


def test_dirty_block_mask_compares_bits():
    """Dirty means changed bits: a NaN kept as is is clean, a sign flip of
    zero is dirty (value comparison would say the opposite of both)."""
    x = np.array([np.nan, 0.0, 1.0, 2.0], np.float32)
    p = np.array([np.nan, -0.0, 1.0, 2.0], np.float32)
    got = np.asarray(dirty_block_mask(jnp.asarray(x), jnp.asarray(p), block_elems=1))
    assert got.tolist() == [0, 1, 0, 0]
    ref = dirty_block_mask_reference(jnp.asarray(x)[:, None], jnp.asarray(p)[:, None])
    assert np.asarray(ref).tolist() == [0, 1, 0, 0]


# ------------------------------------------------------- delta persistence
def _persist_series(n, dtype, rng):
    """A value trajectory that touches one block per step plus the tail."""
    if np.dtype(dtype).kind == "i":
        x = rng.integers(-1000, 1000, size=n).astype(dtype)
    else:
        x = rng.standard_normal(n).astype(np.float32).astype(dtype)
    series = [x]
    for step in range(1, 5):
        x = x.copy()
        x[(step * 17) % n] += np.asarray(1, dtype)
        x[n - 1] += np.asarray(1, dtype)  # partial tail block goes dirty too
        series.append(x)
    return series


@pytest.mark.parametrize("dtype", [np.float32, np.int32, "bfloat16"])
@pytest.mark.parametrize("n", [1, 7, 255, 256, 257, 1000, 4097])
def test_delta_persist_image_matches_full(n, dtype):
    """persist_mode='delta' must leave a byte-identical NVM image to a
    whole-object persist across dtypes and non-multiple-of-block shapes,
    while writing only the blocks the CPU reference finds dirty."""
    if dtype == "bfloat16":
        dtype = jnp.bfloat16.dtype
    rng = np.random.default_rng(n)
    series = _persist_series(n, dtype, rng)

    def run(mode):
        arena = NVMArena(block_bytes=64)
        mgr = EasyCrashManager(arena, FlushPolicy(leaves=("x",), persist_mode=mode))
        for step, x in enumerate(series, start=1):
            mgr.maybe_flush(step, {"x": x})
        return arena.get("x"), mgr.stats.blocks_written

    img_delta, blocks_delta = run("delta")
    img_full, blocks_full = run("full")
    assert img_delta.tobytes() == img_full.tobytes()
    assert img_delta.dtype == np.dtype(dtype)
    # delta: every block at the first flush, then what block_diff_mask finds;
    # the persisted step is one more block at every flush
    blocks = -(-series[0].nbytes // 64)
    dirty = blocks + sum(int(block_diff_mask(a, b, 64).sum())
                         for a, b in zip(series, series[1:]))
    assert blocks_delta == dirty + len(series)
    assert blocks_full == (blocks + 1) * len(series)
    if n > 256:  # multi-block object: the savings must be real
        assert blocks_delta < blocks_full


@pytest.mark.parametrize("dtype", [np.float32, np.int32, "bfloat16"])
@pytest.mark.parametrize("n", [1, 7, 255, 257, 1000, 4097])
def test_delta_block_mask_matches_cpu_reference(n, dtype):
    """The kernel-backed byte-view mask is the CPU block_diff_mask, exactly."""
    if dtype == "bfloat16":
        dtype = jnp.bfloat16.dtype
    rng = np.random.default_rng(n + 1)
    series = _persist_series(n, dtype, rng)
    for cur, live in zip(series, series[1:]):
        got = delta_block_mask(cur, live, block_bytes=64)
        ref = block_diff_mask(cur, live, block_bytes=64)
        np.testing.assert_array_equal(got, ref)
        clean = delta_block_mask(live, live, block_bytes=64)
        assert not clean.any()


@pytest.mark.parametrize("nbytes", [4, 8, 64 * 129 - 5])
def test_the_mask_of_host_zeros_is_clean(nbytes):
    """The benchmark's warm-up call: host uint8 zeros give every block
    clean, one host flag a block."""
    zero = np.zeros(nbytes, np.uint8)
    got = delta_block_mask(zero, zero, 64)
    assert got.dtype == bool and got.shape == (-(-nbytes // 64),) and not got.any()


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
@pytest.mark.parametrize("n", [1, 7, 257, 4097])
def test_a_device_value_is_masked_as_its_bytes_differ(n, dtype):
    """Flat device values in their own dtype give the host byte mask."""
    if dtype == "bfloat16":
        dtype = jnp.bfloat16.dtype
    series = _persist_series(n, dtype, np.random.default_rng(n + 2))
    for cur, live in zip(series, series[1:]):
        got = delta_block_mask(jnp.asarray(cur), jnp.asarray(live), block_bytes=64)
        np.testing.assert_array_equal(got, block_diff_mask(cur, live, block_bytes=64))
        assert got.dtype == bool
        assert not delta_block_mask(jnp.asarray(live), jnp.asarray(live), 64).any()


def test_the_trainers_host_flushes_take_the_byte_masks(tmp_path, monkeypatch):
    """``launch/train.py`` hands the manager host state: its masks are
    computed on host bytes and are the CPU reference's."""
    from repro.core import delta_persist
    from repro.launch import train

    seen = []
    real = delta_persist.delta_block_mask

    def check(cur, live, block_bytes=64):
        got = real(cur, live, block_bytes)
        seen.append(isinstance(cur, np.ndarray) and isinstance(live, np.ndarray)
                    and np.array_equal(got, block_diff_mask(cur, live, block_bytes)))
        return got

    monkeypatch.setattr(delta_persist, "delta_block_mask", check)
    train.main(["--steps", "6", "--flush-every", "2", "--width", "32", "--seq", "16",
                "--batch", "2", "--log-every", "100", "--workdir", str(tmp_path)])
    assert len(seen) > 2 and all(seen)


# ------------------------------------------------------------------ rwkv6
def _rwkv_inputs(b, t, h, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    r = jax.random.normal(ks[0], (b, t, h, d), dtype) * 0.5
    k = jax.random.normal(ks[1], (b, t, h, d), dtype) * 0.5
    v = jax.random.normal(ks[2], (b, t, h, d), dtype) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, d), jnp.float32)).astype(dtype)
    u = jax.random.normal(ks[4], (h, d), jnp.float32) * 0.3
    return r, k, v, w, u


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,d", [(1, 40, 2, 16), (2, 50, 1, 32), (1, 97, 2, 16)])
def test_rwkv6_scan_differential_odd_t(b, t, h, d, dtype):
    """Sequence lengths that are not a multiple of the default time block:
    the kernel must clamp its chunk to T and still match the reference."""
    r, k, v, w, u = _rwkv_inputs(b, t, h, d, dtype)
    out = rwkv6_scan(r, k, v, w, u)  # default block_t=256 > t
    ref = rwkv6_reference(
        jnp.swapaxes(r, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), jnp.swapaxes(w, 1, 2), u,
    )
    ref = jnp.swapaxes(ref, 1, 2)
    assert out.shape == (b, t, h, d)
    tol = 1e-4 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("t,bt", [(96, 24), (60, 20), (144, 48)])
def test_rwkv6_scan_differential_odd_chunks(t, bt):
    """Non-power-of-two chunk sizes tile T exactly and match both the
    reference and the single-chunk evaluation."""
    r, k, v, w, u = _rwkv_inputs(1, t, 2, 16, jnp.float32, seed=3)
    chunked = rwkv6_scan(r, k, v, w, u, block_t=bt)
    whole = rwkv6_scan(r, k, v, w, u, block_t=t)
    ref = rwkv6_reference(
        jnp.swapaxes(r, 1, 2), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), jnp.swapaxes(w, 1, 2), u,
    )
    ref = jnp.swapaxes(ref, 1, 2)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole), atol=1e-5)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(ref), atol=1e-4, rtol=1e-4)
