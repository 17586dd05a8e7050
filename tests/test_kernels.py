"""Per-kernel correctness: shape/dtype sweeps vs the ref.py oracles,
executed with interpret=True on CPU (the TPU-target contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.losses import ntxent_supervised
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# NT-Xent
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,D", [(16, 32), (32, 64), (100, 48), (256, 64),
                                 (64, 17)])
@pytest.mark.parametrize("n_classes", [2, 5])
def test_ntxent_matches_oracle(B, D, n_classes):
    q = jnp.asarray(RNG.normal(size=(B, D)), jnp.float32)
    y = jnp.asarray(RNG.integers(0, n_classes, B), jnp.int32)
    got = float(ops.ntxent_loss(q, y, interpret=True))
    want = float(ntxent_supervised(q, y))
    assert abs(got - want) < 1e-3 * max(1.0, abs(want))


def test_ntxent_stats_match_ref():
    from repro.kernels.ntxent import ntxent_stats
    B, D = 48, 24
    q = jnp.asarray(RNG.normal(size=(B, D)), jnp.float32)
    y = jnp.asarray(RNG.integers(0, 3, B), jnp.int32)
    lse, ps, pc = ntxent_stats(q, y, 0.07, interpret=True)
    rl, rp, rc = ref.ntxent_stats_ref(q, y, 0.07)
    np.testing.assert_allclose(lse, rl, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ps, rp, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(pc, rc)


def test_ntxent_no_positives_is_zero():
    q = jnp.asarray(RNG.normal(size=(4, 8)), jnp.float32)
    y = jnp.arange(4, dtype=jnp.int32)  # all distinct labels
    assert float(ops.ntxent_loss(q, y, interpret=True)) == 0.0


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,S,hd", [
    (2, 4, 2, 256, 64), (1, 8, 8, 128, 32), (2, 8, 2, 512, 64),
    (1, 2, 1, 128, 128),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                           (False, 0)])
def test_flash_attention_matches_oracle(B, Hq, Hkv, S, hd, causal, window):
    q = jnp.asarray(RNG.normal(size=(B, Hq, S, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    B, Hq, Hkv, S, hd = 1, 4, 2, 128, 64
    q = jnp.asarray(RNG.normal(size=(B, Hq, S, hd))).astype(dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, hd))).astype(dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, hd))).astype(dtype)
    got = ops.flash_attention(q, k, v, interpret=True)
    assert got.dtype == dtype
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32),
                               rtol=5e-2, atol=5e-2)


def test_flash_attention_vs_model_chunked():
    """Kernel vs the XLA reference path used by the model stack."""
    from repro.models.attention import mha_chunked
    B, Hq, Hkv, S, hd = 2, 4, 2, 256, 64
    q = jnp.asarray(RNG.normal(size=(B, S, Hq, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), jnp.float32)
    xla = mha_chunked(q, k, v, causal=True, q_chunk=128, kv_chunk=128)
    krn = ops.flash_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True,
                              interpret=True)
    np.testing.assert_allclose(krn.transpose(0, 2, 1, 3), xla,
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Soft threshold + masked Adam
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(64,), (37, 91), (3, 5, 7),
                                   (256, 256), (1000,)])
@pytest.mark.parametrize("t", [0.0, 0.1, 1.5])
def test_soft_threshold(shape, t):
    x = jnp.asarray(RNG.normal(size=shape), jnp.float32)
    got = ops.soft_threshold(x, t, interpret=True)
    want = ref.soft_threshold_ref(x, t)
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("shape", [(16, 16), (33, 47), (7,), (4, 5, 6)])
@pytest.mark.parametrize("step", [1, 10])
def test_masked_adam(shape, step):
    p = jnp.asarray(RNG.normal(size=shape), jnp.float32)
    g = jnp.asarray(RNG.normal(size=shape), jnp.float32)
    mu = jnp.asarray(RNG.normal(size=shape), jnp.float32) * 0.1
    nu = jnp.abs(jnp.asarray(RNG.normal(size=shape), jnp.float32)) * 0.1
    mask = jnp.asarray(RNG.integers(0, 2, shape), jnp.float32)
    got = ops.masked_adam(p, g, mu, nu, mask, step=step, lr=1e-3,
                          interpret=True)
    want = ref.masked_adam_ref(p, g, mu, nu, mask, lr=1e-3, b1=0.9,
                               b2=0.999, eps=1e-8,
                               b1t=1 - 0.9 ** step, b2t=1 - 0.999 ** step)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_masked_adam_zero_mask_freezes_params():
    shape = (32, 32)
    p = jnp.asarray(RNG.normal(size=shape), jnp.float32)
    g = jnp.asarray(RNG.normal(size=shape), jnp.float32)
    zero = jnp.zeros(shape)
    new_p, mu, nu = ops.masked_adam(p, g, zero, zero, zero, step=1,
                                    interpret=True)
    np.testing.assert_allclose(new_p, p)
    np.testing.assert_allclose(mu, 0.0)
