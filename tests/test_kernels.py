"""Per-kernel validation: Pallas (interpret=True) vs ref.py oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.bitmask_join import bitmask_join_pallas
from repro.kernels.clockscan import clockscan_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.shared_groupby import shared_groupby_pallas

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("C,T,Q", [
    (1, 256, 32), (3, 512, 64), (4, 1024, 256), (2, 2048, 128),
])
def test_clockscan_matches_ref(C, T, Q):
    cols = jnp.asarray(RNG.integers(-50, 100, (C, T)), jnp.int32)
    lo = jnp.asarray(RNG.integers(-60, 50, (C, Q)), jnp.int32)
    hi = lo + jnp.asarray(RNG.integers(0, 80, (C, Q)), jnp.int32)
    valid = jnp.asarray(RNG.random(T) > 0.15)
    got = clockscan_pallas(cols, lo, hi, valid, interpret=True)
    want = ref.clockscan_ref(cols, lo, hi, valid)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_clockscan_bounds_inclusive():
    cols = jnp.asarray([[5, 6, 7]], jnp.int32)
    lo = jnp.full((1, 32), 5, jnp.int32)
    hi = jnp.full((1, 32), 6, jnp.int32)
    valid = jnp.ones(3, bool)
    got = np.asarray(clockscan_pallas(
        jnp.pad(cols, ((0, 0), (0, 253))), lo, hi,
        jnp.pad(valid, (0, 253)), interpret=True))
    bits = got[:3, 0] & 1
    assert bits.tolist() == [1, 1, 0]


@pytest.mark.parametrize("Tl,Tr,W", [
    (256, 256, 1), (512, 256, 2), (1024, 512, 8), (256, 1024, 4),
])
def test_bitmask_join_matches_ref(Tl, Tr, W):
    keys_r = jnp.asarray(RNG.permutation(Tr * 3)[:Tr], jnp.int32)
    keys_l = jnp.asarray(RNG.choice(Tr * 4, Tl), jnp.int32)
    mask_l = jnp.asarray(RNG.integers(0, 2**32, (Tl, W)), jnp.uint32)
    mask_r = jnp.asarray(RNG.integers(0, 2**32, (Tr, W)), jnp.uint32)
    valid_r = jnp.asarray(RNG.random(Tr) > 0.25)
    r1, m1 = bitmask_join_pallas(keys_l, mask_l, keys_r, mask_r, valid_r,
                                 interpret=True)
    r2, m2 = ref.bitmask_join_ref(keys_l, mask_l, keys_r, mask_r, valid_r)
    assert (np.asarray(r1) == np.asarray(r2)).all()
    assert (np.asarray(m1) == np.asarray(m2)).all()


@pytest.mark.parametrize("T,W,G", [
    (512, 1, 50), (512, 2, 100), (1024, 8, 300), (2048, 4, 1000),
])
def test_shared_groupby_matches_ref(T, W, G):
    gc = jnp.asarray(RNG.integers(0, G, (T,)), jnp.int32)
    vals = jnp.asarray(RNG.integers(-20, 50, (T,)), jnp.int32)
    mask = jnp.asarray(RNG.integers(0, 2**32, (T, W)), jnp.uint32)
    c1, s1 = shared_groupby_pallas(gc, vals, mask, G, interpret=True)
    c2, s2 = ref.shared_groupby_ref(gc, vals, mask, G)
    np.testing.assert_allclose(c1, c2, rtol=1e-6)
    np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("B,Sq,Sk,H,KV,D,causal,window", [
    (1, 128, 128, 4, 4, 64, True, 0),
    (2, 256, 256, 8, 2, 64, True, 0),
    (2, 256, 256, 8, 4, 32, True, 64),
    (1, 128, 256, 4, 1, 128, False, 0),   # cross-attention-like
    (2, 128, 128, 4, 4, 64, True, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, Sq, Sk, H, KV, D, causal, window,
                                     dtype):
    q = jnp.asarray(RNG.standard_normal((B, Sq, H, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Sk, KV, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Sk, KV, D)), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 5)


def test_flash_attention_matches_model_block_attention():
    """The Pallas kernel and the model-side chunked attention agree."""
    from repro.models.common import block_attention
    B, S, H, KV, D = 2, 256, 8, 4, 64
    q = jnp.asarray(RNG.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, KV, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, KV, D)), jnp.float32)
    a = flash_attention_pallas(q, k, v, causal=True, window=0,
                               interpret=True)
    b = block_attention(q, k, v, causal=True, window=0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=1e-5)


def test_ssd_chunked_matches_naive_recurrence():
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n = 2, 64, 4, 8, 16
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.random((b, s, h)) * 0.5 + 0.1, jnp.float32)
    A = -jnp.asarray(RNG.random(h) + 0.2, jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, s, n)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, s, n)), jnp.float32)
    y1, f1 = ssd_chunked(x, dt, A, B, C, chunk=16)
    y2, f2 = ref.ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(f1, f2, rtol=2e-4, atol=2e-4)
