"""Cross-path consistency properties: the same math must come out of
the train/prefill path and the decode path (cache-carried recurrences),
and kernels must agree with oracles on randomized shapes (hypothesis)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_config


def test_mamba_prefill_state_equals_decode_replay():
    """SSD chunked forward's final state == token-by-token recurrence."""
    from repro.models import ssm
    cfg = get_config("mamba2-370m").reduced()
    p = ssm.mamba_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    B, L = 2, 32
    x = jnp.asarray(rng.normal(0, 0.3, (B, L, cfg.d_model)), jnp.float32)

    out_full, st_full = ssm.mamba_forward(p, x, cfg, return_state=True)

    cache = ssm.init_ssm_cache(cfg, B, jnp.float32)
    outs = []
    for t in range(L):
        o, cache = ssm.mamba_decode(p, x[:, t:t + 1], cache, cfg)
        outs.append(o)
    out_dec = jnp.concatenate(outs, axis=1)

    np.testing.assert_allclose(np.asarray(out_dec), np.asarray(out_full),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(cache["state"]),
                               np.asarray(st_full["state"]),
                               rtol=2e-2, atol=2e-2)


def test_kv_decode_replay_matches_full_attention():
    """attn_decode over a ring-free cache == full causal attention."""
    from repro.models import attention as attn
    cfg = get_config("qwen2-0.5b").reduced()
    p = attn.attention_init(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    B, L = 2, 12
    x = jnp.asarray(rng.normal(0, 0.3, (B, L, cfg.d_model)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    out_full, _ = attn.attn_forward(p, x, cfg, positions=pos, causal=True)

    cache = attn.init_kv_cache(cfg, B, L, jnp.float32)
    outs = []
    for t in range(L):
        o, cache = attn.attn_decode(p, x[:, t:t + 1], cache,
                                    jnp.asarray(t, jnp.int32), cfg)
        outs.append(o)
    out_dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_dec), np.asarray(out_full),
                               rtol=2e-2, atol=2e-2)


def test_windowed_decode_matches_windowed_forward():
    from repro.models import attention as attn
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced())
    p = attn.attention_init(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(2)
    B, L, W = 1, 16, 4
    x = jnp.asarray(rng.normal(0, 0.3, (B, L, cfg.d_model)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    out_full, _ = attn.attn_forward(p, x, cfg, positions=pos, causal=True,
                                    window=W)
    cache = attn.init_kv_cache(cfg, B, W, jnp.float32)  # ring of W
    outs = []
    for t in range(L):
        o, cache = attn.attn_decode(p, x[:, t:t + 1], cache,
                                    jnp.asarray(t, jnp.int32), cfg,
                                    window=W)
        outs.append(o)
    out_dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_dec), np.asarray(out_full),
                               rtol=3e-2, atol=3e-2)


@given(b=st.integers(8, 48), d=st.integers(8, 40),
       n_classes=st.integers(2, 6), seed=st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_ntxent_kernel_matches_oracle_randomized(b, d, n_classes, seed):
    from repro.core.losses import ntxent_supervised
    from repro.kernels import ops
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, n_classes, b), jnp.int32)
    got = float(ops.ntxent_loss(q, y, interpret=True))
    want = float(ntxent_supervised(q, y))
    assert abs(got - want) <= 1e-3 * max(1.0, abs(want))


@given(st.sampled_from([32, 64, 128]), st.sampled_from([1, 2]),
       st.sampled_from([16, 32]), st.booleans(), st.integers(0, 50))
@settings(max_examples=12, deadline=None)
def test_flash_kernel_matches_oracle_randomized(S, hkv, hd, causal, seed):
    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed)
    Hq = hkv * 2
    q = jnp.asarray(rng.normal(size=(1, Hq, S, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, hkv, S, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, hkv, S, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=32,
                              block_k=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@given(st.integers(3, 12), st.floats(0.1, 0.99), st.integers(0, 20))
@settings(max_examples=15, deadline=None)
def test_orchestrator_invariants(n, eta, seed):
    from repro.core.orchestrator import Orchestrator
    o = Orchestrator(n, eta, seed=seed)
    k = max(1, int(round(eta * n)))
    for _ in range(5):
        sel = o.select()
        assert len(sel) == k and len(set(sel.tolist())) == k
        assert all(0 <= i < n for i in sel)
        o.update(sel, [float(np.random.default_rng(seed).uniform(0, 10))
                       for _ in sel])
        a = o.advantage()
        assert np.isfinite(a).all()
