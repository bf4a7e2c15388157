"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention import attention_ref, flash_attention
from repro.kernels.checksum import checksum_ref, device_checksum, verify_replicas
from repro.kernels.rglru import rglru_scan, rglru_scan_ref
from repro.kernels.ssd import ssd_mixer, ssd_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,skv,hq,hkv,d,causal,window,cap",
    [
        (2, 128, 128, 4, 2, 64, True, 0, 0.0),
        (1, 192, 192, 4, 4, 32, True, 0, 50.0),    # softcap (gemma2)
        (2, 256, 256, 8, 2, 64, True, 64, 0.0),    # sliding window
        (1, 64, 320, 2, 1, 128, False, 0, 0.0),    # cross-shape, MQA
        (1, 130, 130, 2, 2, 16, True, 0, 0.0),     # non-multiple of block
    ],
)
def test_flash_attention_vs_ref(b, sq, skv, hq, hkv, d, causal, window, cap, dtype):
    q = jnp.asarray(RNG.normal(size=(b, sq, hq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=64, block_kv=64)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("b,s,w,bt", [(2, 64, 32, 16), (1, 300, 100, 128),
                                      (3, 512, 256, 256), (1, 16, 8, 16)])
def test_rglru_vs_ref(b, s, w, bt):
    a = jnp.asarray(RNG.uniform(0.3, 0.999, (b, s, w)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(b, s, w)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(b, w)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(rglru_scan(a, x, h0, block_t=bt)),
        np.asarray(rglru_scan_ref(a, x, h0)),
        atol=1e-4, rtol=1e-4,
    )


@pytest.mark.parametrize("b,h,s,p,n,q", [(2, 4, 64, 16, 16, 16),
                                         (1, 2, 130, 32, 64, 32),
                                         (2, 8, 256, 64, 128, 64)])
def test_ssd_vs_ref(b, h, s, p, n, q):
    x = jnp.asarray(RNG.normal(size=(b, h, s, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, h, s)), jnp.float32)
    a = jnp.asarray(-RNG.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    out = np.asarray(ssd_mixer(x, dt, a, B, C, chunk=q))
    ref = np.asarray(ssd_ref(x, dt, a, B, C, q))
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_checksum_vs_ref_and_detects_corruption():
    x = jnp.asarray(RNG.integers(0, 2**31 - 1, 4096), jnp.int32)
    got = device_checksum(x, block=512)
    want = checksum_ref(x.astype(jnp.uint32), block=512)
    assert bool((got == want).all())
    y = x.at[1234].set(x[1234] ^ 1)
    assert not bool((device_checksum(y, block=512) == got).all())
    assert verify_replicas([got, got, got])
    assert not verify_replicas([got, device_checksum(y, block=512)])


@pytest.mark.parametrize("dtype,lo,hi", [
    (np.uint8, 0, 256),             # byte tiles, widened inside the kernel
    (np.int32, -2**31, 2**31 - 1),  # high bit set: unsigned words mod 65521
])
def test_checksum_full_word_range_vs_ref(dtype, lo, hi):
    x = RNG.integers(lo, hi, (64, 128), endpoint=True).astype(dtype)
    got = device_checksum(jnp.asarray(x), block=2048)
    want = checksum_ref(jnp.asarray(x.reshape(-1)).astype(jnp.uint32),
                        block=2048)
    assert bool((got == want).all())


def test_checksum_any_dtype():
    f = jnp.asarray(RNG.normal(size=(33, 65)), jnp.float32)
    c1, c2 = device_checksum(f), device_checksum(f + 1e-3)
    assert not bool((c1 == c2).all())


def test_flash_attention_matches_model_path():
    """Kernel vs the model's XLA chunked-attention implementation."""
    from repro.models.attention import flash_attention as xla_flash
    q = jnp.asarray(RNG.normal(size=(2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 128, 2, 32)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    b = xla_flash(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)
