import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="property tests need hypothesis (pip install -e .[dev])")
from hypothesis import given, settings, strategies as st

from repro.core.blocks import (
    block_diff_mask,
    inconsistent_rate,
    mix_blocks,
    mix_blocks_into,
    num_blocks,
)


def test_num_blocks():
    assert num_blocks(0) == 0
    assert num_blocks(1) == 1
    assert num_blocks(64) == 1
    assert num_blocks(65) == 2
    assert num_blocks(128, block_bytes=32) == 4


def test_mix_blocks_basic():
    old = np.zeros(32, np.float32)   # 128 B = 2 blocks
    new = np.ones(32, np.float32)
    out = mix_blocks(old, new, np.array([True, False]))
    assert (out[:16] == 1).all() and (out[16:] == 0).all()


def test_mix_blocks_partial_tail():
    old = np.zeros(20, np.float32)   # 80 B = 2 blocks (2nd partial)
    new = np.ones(20, np.float32)
    out = mix_blocks(old, new, np.array([False, True]))
    assert (out[:16] == 0).all() and (out[16:] == 1).all()


def test_inconsistent_rate():
    a = np.zeros(16, np.float32)
    b = a.copy()
    assert inconsistent_rate(a, b) == 0.0
    b[0] = 1.0
    assert 0 < inconsistent_rate(a, b) <= 4 / 64


@given(
    n=st.integers(1, 200),
    seed=st.integers(0, 2**31 - 1),
    block_bytes=st.sampled_from([16, 64, 128]),
)
@settings(max_examples=50, deadline=None)
def test_mix_blocks_roundtrip(n, seed, block_bytes):
    rng = np.random.default_rng(seed)
    old = rng.standard_normal(n).astype(np.float32)
    new = rng.standard_normal(n).astype(np.float32)
    nb = num_blocks(old.nbytes, block_bytes)
    # all-new mask reproduces new; all-old reproduces old
    assert np.array_equal(mix_blocks(old, new, np.ones(nb, bool), block_bytes), new)
    assert np.array_equal(mix_blocks(old, new, np.zeros(nb, bool), block_bytes), old)
    # a random mask only ever takes bytes from old or new
    mask = rng.random(nb) < 0.5
    out = mix_blocks(old, new, mask, block_bytes)
    ob = out.view(np.uint8)
    for src in (old, new):
        pass
    takes = (ob == old.view(np.uint8)) | (ob == new.view(np.uint8))
    assert takes.all()


@given(n=st.integers(1, 100), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=50, deadline=None)
def test_block_diff_mask_matches_mix(n, seed):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal(n).astype(np.float32)
    b = a.copy()
    nb = num_blocks(a.nbytes)
    flip = rng.integers(0, n)
    b[flip] += 1.0
    mask = block_diff_mask(a, b)
    assert mask.shape == (nb,)
    assert mask.sum() == 1
    assert mask[(flip * 4) // 64]
    # mixing b into a along the diff mask reproduces b
    assert np.array_equal(mix_blocks(a, b, mask), b)


def _byte_mask_mix(old, new, mask, block_bytes):
    """The per-byte formula ``mix_blocks`` used before its row merge: the
    oracle its in-place and copying forms are held to."""
    ob = np.ascontiguousarray(old).view(np.uint8).reshape(-1).copy()
    nbv = np.ascontiguousarray(new).view(np.uint8).reshape(-1)
    byte_mask = np.repeat(np.asarray(mask, bool), block_bytes)[: ob.size]
    ob[byte_mask] = nbv[byte_mask]
    return ob.view(old.dtype).reshape(old.shape)


def _values(dtype, n, rng):
    if np.dtype(dtype).kind == "i":
        return rng.integers(-128, 128, n).astype(dtype)
    return rng.standard_normal(n).astype(dtype)


def _mask(kind, nb, rng):
    if kind == "all":
        return np.ones(nb, bool)
    if kind == "empty":
        return np.zeros(nb, bool)
    if kind == "random":
        return rng.random(nb) < 0.3
    mask = np.zeros(nb, bool)  # one contiguous run
    mask[nb // 3: 2 * nb // 3 + 1] = True
    return mask


@pytest.mark.parametrize("mask_kind", ["all", "empty", "random", "run"])
@pytest.mark.parametrize("tail", ["aligned", "partial_dirty", "partial_clean"])
@pytest.mark.parametrize("block_bytes", [16, 64, 128])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32", "int8", "float64"])
def test_row_merge_matches_the_byte_mask_formula(dtype, block_bytes, tail, mask_kind):
    import ml_dtypes

    dt = np.dtype(ml_dtypes.bfloat16) if dtype == "bfloat16" else np.dtype(dtype)
    rng = np.random.default_rng(block_bytes * dt.itemsize)
    n = 37 * block_bytes // dt.itemsize + (1 if tail != "aligned" else 0)
    old, new = _values(dt, n, rng), _values(dt, n, rng)
    nb = num_blocks(old.nbytes, block_bytes)
    assert (old.nbytes % block_bytes != 0) == (tail != "aligned")
    mask = _mask(mask_kind, nb, rng)
    if tail != "aligned":
        mask[-1] = tail == "partial_dirty"
    want = _byte_mask_mix(old, new, mask, block_bytes)
    kept = old.copy()

    out = mix_blocks(old, new, mask, block_bytes)
    assert out.dtype == dt and out.shape == old.shape
    assert out.tobytes() == want.tobytes()
    assert old.tobytes() == kept.tobytes()  # mix_blocks leaves ``old`` alone

    dst = old.copy()
    mix_blocks_into(dst, new, mask, block_bytes)
    assert dst.tobytes() == want.tobytes()


def _mismatch(kind):
    old = np.zeros((4, 8), np.float32)      # 128 B: 2 blocks of 64
    new, mask = np.ones((4, 8), np.float32), np.array([True, False])
    if kind == "shape":
        new = new.reshape(8, 4)
    elif kind == "dtype":
        new = new.view(np.int32)
    elif kind == "mask_length":
        mask = np.array([True, False, True])
    return old, new, mask


@pytest.mark.parametrize("kind", ["shape", "dtype", "mask_length"])
@pytest.mark.parametrize("merge", ["mix_blocks", "mix_blocks_into"])
def test_merges_reject_a_mismatch(merge, kind):
    old, new, mask = _mismatch(kind)
    fn = mix_blocks if merge == "mix_blocks" else mix_blocks_into
    with pytest.raises(ValueError):
        fn(old, new, mask)
    assert not old.any()


@pytest.mark.parametrize("dst", ["read_only", "strided"])
def test_in_place_merge_needs_a_writable_contiguous_image(dst):
    base = np.zeros((4, 16), np.float32)
    if dst == "read_only":
        img = base
        img.flags.writeable = False
    else:
        img = base[:, ::2]
    with pytest.raises(ValueError):
        mix_blocks_into(img, np.ones_like(img), np.ones(2, bool))
    assert not base.any()
