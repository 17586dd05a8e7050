"""GQA attention: init, train/prefill forward (chunked online-softmax),
single-token decode with (optionally windowed ring-buffer) KV cache.

The chunked path is the XLA reference implementation of the Pallas flash
kernel in ``repro.kernels.flash_attention`` — same math, scan-blocked so
the HLO stays small and the working set bounded for 32k+ sequences.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_mrope, apply_rope, dense_init

NEG_INF = -1e30


def attention_init(key, cfg):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq * hd),
        "wk": dense_init(ks[1], d, hkv * hd),
        "wv": dense_init(ks[2], d, hkv * hd),
        "wo": dense_init(ks[3], hq * hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((hkv * hd,), jnp.float32)
    return p


def _project_qkv(p, x, cfg, dtype):
    B, S, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"].astype(dtype)
    k = x @ p["wk"].astype(dtype)
    v = x @ p["wv"].astype(dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    return (q.reshape(B, S, hq, hd), k.reshape(B, S, hkv, hd),
            v.reshape(B, S, hkv, hd))


def _rope_qk(q, k, cfg, positions):
    if positions is None:
        return q, k
    if cfg.mrope_sections:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def mha_einsum(q, k, v, *, causal: bool, window: int = 0,
               q_offset: int = 0, kv_valid: Optional[jnp.ndarray] = None):
    """Plain einsum attention (small shapes / oracle).

    q: (B, Sq, Hq, hd); k, v: (B, Sk, Hkv, hd).  GQA via head grouping.
    Serves training, prefill and the tests' oracle.  Single-token decode
    has its own grouped path, :func:`gqa_decode`: a decode step reads the
    whole cache to make one token, so the float32 K/V repeated to Hq
    heads here would be written and read back for every layer and step,
    many times the bytes of the cache itself.
    """
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qf = q.astype(jnp.float32)
    # repeat kv heads to Hq (exact GQA math).  Keeping heads FLAT — rather
    # than factoring (Hkv, G) — lets a head-sharded `model` axis propagate
    # through every einsum with no resharding.
    kf = jnp.repeat(k.astype(jnp.float32), G, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), G, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / jnp.sqrt(hd)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    if kv_valid is not None:  # (B, Sk) bool
        scores = jnp.where(kv_valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, vf)
    return out.astype(q.dtype)


def gqa_decode(q, k, v, kv_valid: Optional[jnp.ndarray] = None):
    """Single-token attention on grouped KV heads, reading the cache once.

    q: (B, 1, Hq, hd); k, v: (B, L, Hkv, hd) in the cache's dtype;
    kv_valid: optional (B, L) bool.  q is viewed as (B, 1, Hkv, G, hd),
    so K and V are never repeated to Hq heads nor upcast as a whole.
    Score products accumulate in float32 (bfloat16 products are exact
    there); the float32 probabilities meet V at ``HIGHEST`` precision.
    """
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) / jnp.sqrt(hd)
    if kv_valid is not None:  # (B, L) bool
        scores = jnp.where(kv_valid[:, None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)


def mha_chunked(q, k, v, *, causal: bool, window: int = 0,
                q_chunk: int = 1024, kv_chunk: int = 1024):
    """Flash-style online-softmax attention, scan-blocked over q and kv.

    Memory is O(q_chunk * kv_chunk) per head instead of O(S^2); this is
    the sequence path used for train/prefill at long S.
    """
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq, nk = Sq // q_chunk, Skv // kv_chunk
    assert Sq % q_chunk == 0 and Skv % kv_chunk == 0

    # heads stay FLAT (kv repeated per-block) so a head-sharded `model`
    # axis propagates with no resharding; repeat cost is one block.
    # dots run on bf16 inputs with f32 accumulation (flash practice).
    qf = q.astype(jnp.bfloat16).reshape(B, nq, q_chunk, Hq, hd)
    kf = k.astype(jnp.bfloat16).reshape(B, nk, kv_chunk, Hkv, hd)
    vf = v.astype(jnp.bfloat16).reshape(B, nk, kv_chunk, Hkv, hd)
    scale = 1.0 / jnp.sqrt(hd)

    def q_body(_, qi):
        qblk, qidx = qi                       # (B, qc, Hq, hd), scalar
        qpos = qidx * q_chunk + jnp.arange(q_chunk)

        def kv_body(carry, ki):
            m, l, acc = carry
            kblk, vblk, kidx = ki
            kblk = jnp.repeat(kblk, G, axis=2)          # (B, kc, Hq, hd)
            vblk = jnp.repeat(vblk, G, axis=2)
            kpos = kidx * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            msk = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                msk &= kpos[None, :] <= qpos[:, None]
            if window:
                msk &= kpos[None, :] > (qpos[:, None] - window)
            s = jnp.where(msk[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(jnp.bfloat16), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, Hq, q_chunk), NEG_INF, jnp.float32),
            jnp.zeros((B, Hq, q_chunk), jnp.float32),
            jnp.zeros((B, Hq, q_chunk, hd), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            kv_body, init,
            (kf.swapaxes(0, 1), vf.swapaxes(0, 1), jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B,Hq,qc,hd)
        return None, out.transpose(0, 2, 1, 3)            # (B,qc,Hq,hd)

    _, outs = jax.lax.scan(q_body, None,
                           (qf.swapaxes(0, 1), jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, Hq, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Layer-level entry points
# ---------------------------------------------------------------------------


def _head_gate(out, gate, dtype):
    """AdaSplit per-head server mask, applied PRE-wo on (B, S, H, hd)."""
    if gate is None:
        return out
    g = gate.astype(dtype)
    g = g[None, None, :, None] if g.ndim == 1 else g[:, None, :, None]
    return out * g


def attn_forward(p, x, cfg, *, positions, causal=True, window=0,
                 chunked=None, kv_override=None, head_gate=None,
                 qkv_shard=None, out_shard=None, kv_valid=None):
    """Full-sequence attention (train / prefill / encoder).

    kv_override: (k, v) already projected — used for cross-attention.
    kv_valid: optional (B, S) bool key-validity mask for ragged batches —
    padded key positions contribute nothing to ANY query (serving
    right-pads ragged prompts; causality already protects real queries
    from trailing pads, the mask makes the invariance explicit and
    covers non-causal uses).  Forces the einsum path.
    head_gate: AdaSplit structured mask, (H,) or (B, H), gating each
    attention head's output before the wo projection (masking a head's
    slice of wo's input = masking that head's parameters, eq. 7).
    qkv_shard: optional PartitionSpec pinned onto q/k/v/out — used by the
    launcher to batch-shard attention over the `model` axis when heads
    don't divide it (attention is parallel over (B, H); replicating it
    across model ranks multiplies score-block HBM traffic by the axis
    size — §Perf pair-1 iteration).
    Returns (out, (k, v)) so prefill can stash the cache.
    """
    dtype = x.dtype
    B, S, _ = x.shape
    if kv_override is None:
        q, k, v = _project_qkv(p, x, cfg, dtype)
        q, k = _rope_qk(q, k, cfg, positions)
    else:
        hq, hd = cfg.n_heads, cfg.head_dim
        q = (x @ p["wq"].astype(dtype))
        if cfg.qkv_bias:
            q = q + p["bq"].astype(dtype)
        q = q.reshape(B, S, hq, hd)
        k, v = kv_override
        causal, window = False, 0
    if qkv_shard is not None:
        # either one spec for q/k/v (batch-over-model) or a (q_spec,
        # kv_spec) pair (sequence-sharded q + gathered k/v, the
        # ring-attention layout that composes with Megatron-SP)
        # NB: a bare PartitionSpec IS a tuple subclass — only a true
        # 2-tuple of specs is the (q_spec, kv_spec) pair form.
        pair = (isinstance(qkv_shard, tuple)
                and not isinstance(qkv_shard, jax.sharding.PartitionSpec))
        qs, kvs = qkv_shard if pair else (qkv_shard, qkv_shard)
        q = jax.lax.with_sharding_constraint(q, qs)
        k = jax.lax.with_sharding_constraint(k, kvs)
        v = jax.lax.with_sharding_constraint(v, kvs)
    if chunked is None:
        chunked = S > 2048
    if kv_valid is not None:
        out = mha_einsum(q, k, v, causal=causal, window=window,
                         kv_valid=kv_valid)
    elif chunked and S % 256 == 0:
        out = mha_chunked(q, k, v, causal=causal, window=window,
                          q_chunk=min(1024, S), kv_chunk=min(1024, k.shape[1]))
    else:
        out = mha_einsum(q, k, v, causal=causal, window=window)
    if out_shard is not None:
        # pin the attention exit BACK to the residual layout so the
        # batch-over-model scatter never leaks into the FFN (where a
        # B-on-model x F-on-model conflict triggers XLA's replicate-
        # everything fallback — §Perf pair-1 it2)
        out = jax.lax.with_sharding_constraint(out, out_shard)
    out = _head_gate(out, head_gate, dtype)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(dtype), (k, v)


def init_kv_cache(cfg, batch, length, dtype):
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, length, hkv, hd), dtype),
        "v": jnp.zeros((batch, length, hkv, hd), dtype),
    }


def attn_decode(p, x, cache, pos, cfg, *, window=0, kv_override=None,
                head_gate=None):
    """One-token decode.  x: (B, 1, D).

    pos is either a scalar int32 (whole batch at the same position — the
    training-adjacent path, bit-identical to the seed) or a (B,) int32
    vector of PER-SLOT positions for continuous-batching serving: each
    row writes its K/V at its own cache slot and only keys at
    ``idx <= pos[b]`` (its own prompt + generated prefix) are attended —
    empty slots and right-pad keys contribute nothing.

    With ``window`` the cache is a ring buffer of that length.
    Returns (out, new_cache).
    """
    dtype = x.dtype
    B = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if kv_override is not None:
        q = (x @ p["wq"].astype(dtype))
        if cfg.qkv_bias:
            q = q + p["bq"].astype(dtype)
        q = q.reshape(B, 1, hq, hd)
        k_all, v_all = kv_override
        with jax.named_scope("decode_attention"):
            out = gqa_decode(q, k_all, v_all)
        out = _head_gate(out, head_gate, dtype)
        out = out.reshape(B, 1, hq * hd)
        return out @ p["wo"].astype(dtype), cache

    q, k, v = _project_qkv(p, x, cfg, dtype)
    posb = pos.reshape(B, 1) if pos.ndim else \
        jnp.broadcast_to(pos[None, None], (B, 1))
    if cfg.mrope_sections:
        posb3 = jnp.broadcast_to(posb[..., None], (B, 1, 3))
        q, k = _rope_qk(q, k, cfg, posb3)
    else:
        q, k = _rope_qk(q, k, cfg, posb)
    L = cache["k"].shape[1]
    idx = jnp.arange(L)
    if pos.ndim:                          # per-slot positions (B,)
        posv = posb[:, 0]
        slot = (posv % L) if window else jnp.minimum(posv, L - 1)
        bidx = jnp.arange(B)
        k_all = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
        v_all = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
        if window:
            kv_valid = idx[None, :] < jnp.minimum(posv + 1, L)[:, None]
        else:
            kv_valid = idx[None, :] <= posv[:, None]
    else:
        slot = (pos % L) if window else jnp.minimum(pos, L - 1)
        k_all = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        v_all = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        if window:
            valid = idx < jnp.minimum(pos + 1, L)  # ring: all valid once full
            # relative recency works without unrolling the ring because
            # softmax is permutation-invariant over kv slots; mask alone
            # suffices.
        else:
            valid = idx <= pos
        kv_valid = jnp.broadcast_to(valid[None, :], (B, L))
    with jax.named_scope("decode_attention"):
        out = gqa_decode(q, k_all, v_all, kv_valid)
    out = _head_gate(out, head_gate, dtype)
    out = out.reshape(B, 1, hq * hd)
    return out @ p["wo"].astype(dtype), {"k": k_all, "v": v_all}


def cross_kv(p, enc_out, cfg, dtype):
    """Project encoder output once into cross-attention K/V."""
    B, S, _ = enc_out.shape
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ p["wk"].astype(dtype))
    v = (enc_out @ p["wv"].astype(dtype))
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    return k.reshape(B, S, hkv, hd), v.reshape(B, S, hkv, hd)
