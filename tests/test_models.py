"""Per-arch smoke tests (deliverable f): every assigned architecture's
REDUCED variant runs one forward + one train step + prefill/decode on
CPU, asserting shapes and finiteness.  Also consistency: prefill+decode
logits must match the full forward at the same position."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, list_archs
from repro import models
from repro.models import decode as dec

ARCHS = list_archs()


def _inputs(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    extras = {}
    if cfg.is_encoder_decoder:
        extras["src_embeds"] = jnp.asarray(
            rng.normal(0, 0.3, (B, S, cfg.d_model)), jnp.float32)
    if cfg.modality == "vision_text":
        extras["vision_embeds"] = jnp.asarray(
            rng.normal(0, 0.3, (B, 8, cfg.d_model)), jnp.float32)
    return tokens, (extras or None)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_finite(arch):
    cfg = get_config(arch).reduced()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    tokens, extras = _inputs(cfg)
    logits, aux = models.forward(cfg, params, tokens, extras)
    assert logits.shape == (2, 32, cfg.padded_vocab())
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))
    # padded vocab rows are masked out
    if cfg.padded_vocab() > cfg.vocab_size:
        assert float(logits[..., cfg.vocab_size:].max()) < -1e8


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch):
    """One composed AdaSplit-style step: client NT-Xent + server CE."""
    from repro.core.losses import cross_entropy, ntxent_supervised
    cfg = get_config(arch).reduced()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    tokens, extras = _inputs(cfg, B=4)
    labels = jnp.roll(tokens, -1, axis=1)
    seq_class = jnp.asarray([0, 0, 1, 1], jnp.int32)

    def loss_fn(params):
        acts = models.client_forward(cfg, params["client"], tokens, extras)
        q = jnp.mean(acts.astype(jnp.float32), axis=1)
        lc = ntxent_supervised(q, seq_class)
        acts_sg = jax.lax.stop_gradient(acts)
        if cfg.is_conv:
            logits, aux = models.server_forward(cfg, params["server"],
                                                acts_sg)
        else:
            logits, aux = models.server_forward(cfg, params["server"],
                                                acts_sg, tokens, extras)
        return lc + cross_entropy(logits, labels) + 0.01 * aux

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss))
    finite = jax.tree.map(
        lambda g: bool(jnp.isfinite(g.astype(jnp.float32)).all()), grads)
    assert all(jax.tree.leaves(finite))
    # stop-grad boundary: server loss must NOT leak grads into client...
    # client grads exist only via the NT-Xent term; check they are finite
    # and that server lm_head got gradient
    lm_g = grads["server"]["lm_head"]["table"]
    assert float(jnp.abs(lm_g).sum()) > 0


@pytest.mark.parametrize("arch", [a for a in ARCHS if a != "lenet-cifar"])
def test_prefill_decode_matches_forward(arch):
    """Greedy next-token from (prefill -> decode_step) == from the full
    forward over the extended sequence."""
    cfg = get_config(arch).reduced()
    params = models.init_params(cfg, jax.random.PRNGKey(1))
    B, S = 2, 16
    tokens, extras = _inputs(cfg, B=B, S=S, seed=3)
    logits_full, _ = models.forward(cfg, params, tokens, extras)

    lg_pref, cache = dec.prefill(cfg, params, tokens, extras,
                                 cache_len=S + 8)
    if not cfg.is_encoder_decoder:
        # enc-dec prefill primes the decoder with BOS only — its logits
        # are for decoder position 0, not the full-tokens forward
        np.testing.assert_allclose(
            np.asarray(lg_pref[:, -1], np.float32),
            np.asarray(logits_full[:, -1], np.float32),
            rtol=6e-2, atol=6e-2)

    # decode one more token and compare against forward on S+1
    nxt = jnp.argmax(lg_pref[:, -1:], axis=-1).astype(jnp.int32)
    lg_dec, _ = dec.decode_step(cfg, params, nxt, cache,
                                jnp.asarray(S, jnp.int32))
    ext = jnp.concatenate([tokens, nxt], axis=1)
    if extras and "src_embeds" in (extras or {}):
        pass  # encoder input unchanged
    lg_full2, _ = models.forward(cfg, params, ext, extras)
    if cfg.is_encoder_decoder:
        # enc-dec prefill primes with BOS only; decode positions differ —
        # just require finiteness for this family
        assert bool(jnp.isfinite(lg_dec.astype(jnp.float32)).all())
    else:
        np.testing.assert_allclose(
            np.asarray(lg_dec[:, 0], np.float32),
            np.asarray(lg_full2[:, -1], np.float32), rtol=8e-2, atol=8e-2)


def test_mamba_chunked_invariant_to_chunk_size():
    from repro.models import ssm
    cfg = get_config("mamba2-370m").reduced()
    p = ssm.mamba_init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(0, 0.3, (2, 64, cfg.d_model)),
                    jnp.float32)
    outs = []
    for chunk in (8, 16, 32):
        cfg2 = cfg if cfg.ssm_chunk == chunk else \
            __import__("dataclasses").replace(cfg, ssm_chunk=chunk)
        outs.append(ssm.mamba_forward(p, x, cfg2))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(outs[0], outs[2], rtol=2e-4, atol=2e-4)


def test_attention_chunked_matches_einsum():
    from repro.models.attention import mha_chunked, mha_einsum
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 512, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 512, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 512, 2, 64)), jnp.float32)
    for causal, win in [(True, 0), (True, 128), (False, 0)]:
        a = mha_einsum(q, k, v, causal=causal, window=win)
        b = mha_chunked(q, k, v, causal=causal, window=win,
                        q_chunk=128, kv_chunk=128)
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)


def _oracle_decode(q, k, v, kv_valid=None):
    from repro.models.attention import mha_einsum
    return mha_einsum(q, k, v, causal=False, kv_valid=kv_valid)


@pytest.mark.parametrize("mode,G", [("slots", 1), ("slots", 2), ("slots", 7),
                                    ("scalar", 7), ("window", 7),
                                    ("cross", 7), ("f32_query", 7)])
def test_gqa_decode_matches_einsum_oracle(monkeypatch, mode, G):
    """attn_decode on grouped KV heads over a bfloat16 cache == the same
    step with ``mha_einsum`` (KV repeated to Hq heads in float32) in its
    place.  Per-slot positions hold an empty slot (no valid key) and a
    slot at the cache's last row; the ring is full and wrapped.  Keys are
    drawn wide enough that the softmax is peaked, so the scale and the
    mask both show.  ``f32_query`` calls gqa_decode itself with a float32
    query: its float32 output must carry the oracle's precision, which
    probabilities rounded to bfloat16 would not."""
    import dataclasses
    from repro.models import attention as attn
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              n_kv_heads=2, n_heads=2 * G)
    bf = jnp.bfloat16
    p = jax.tree.map(lambda a: a.astype(bf),
                     attn.attention_init(jax.random.PRNGKey(3), cfg))
    rng = np.random.default_rng(G)
    B, L = 4, 16
    x = jnp.asarray(rng.normal(0, 1.0, (B, 1, cfg.d_model)), bf)
    cache = {n: jnp.asarray(rng.normal(0, sd, (B, L, cfg.n_kv_heads,
                                               cfg.head_dim)), bf)
             for n, sd in (("k", 3.0), ("v", 1.0))}
    pos = {"slots": jnp.asarray([-1, 0, 9, L - 1], jnp.int32),
           "scalar": jnp.asarray(9, jnp.int32),
           "window": jnp.asarray(L + 5, jnp.int32),
           "cross": jnp.asarray(0, jnp.int32)}.get(mode)
    if mode == "f32_query":
        q = jnp.asarray(rng.normal(0, 1.0, (B, 1, cfg.n_heads,
                                            cfg.head_dim)), jnp.float32)
        valid = jnp.arange(L)[None, :] <= jnp.asarray([-1, 0, 9, L - 1])[:, None]
        got = attn.gqa_decode(q, cache["k"], cache["v"], valid)
        want = _oracle_decode(q, cache["k"], cache["v"], valid)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        return

    def run():
        if mode == "cross":
            return attn.attn_decode(p, x, None, pos, cfg,
                                    kv_override=(cache["k"], cache["v"]))
        return attn.attn_decode(p, x, cache, pos, cfg,
                                window=L if mode == "window" else 0)

    got, got_cache = run()
    monkeypatch.setattr(attn, "gqa_decode", _oracle_decode)
    want, want_cache = run()
    assert got.dtype == bf
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)
    if mode != "cross":
        for n in "kv":
            assert got_cache[n].dtype == bf
            np.testing.assert_array_equal(np.asarray(got_cache[n]),
                                          np.asarray(want_cache[n]))


def test_param_counts_match_model_cards():
    """Analytic param counts should land near the named model sizes."""
    expect = {
        "qwen3-moe-30b-a3b": (30e9, 0.25),
        "jamba-v0.1-52b": (52e9, 0.30),
        "phi3-mini-3.8b": (3.8e9, 0.25),
        "mamba2-370m": (370e6, 0.35),
        "deepseek-moe-16b": (16e9, 0.30),
        "qwen2-vl-72b": (72e9, 0.25),
        "granite-3-8b": (8e9, 0.35),
        "qwen2-0.5b": (0.5e9, 0.35),
        "olmo-1b": (1e9, 0.40),
    }
    for arch, (want, tol) in expect.items():
        got = get_config(arch).param_count()
        assert abs(got - want) / want < tol, (arch, got, want)
