"""Pallas TPU kernel: flash attention (causal / sliding-window GQA).

The backbone hot-spot.  Online-softmax over kv blocks with q/k/v/o tiled
into VMEM; the (bq, bk) score block and the f32 (m, l, acc) accumulators
never leave VMEM — this is exactly the traffic the XLA reference path
(``repro.models.attention.mha_chunked``) materialises to HBM per scan
step, and what the §Perf kernel iteration removes.

Grid: (batch, q_heads, q_blocks, kv_blocks); kv innermost so the
accumulator scratch carries across the kv sweep and is flushed at the
last block.  GQA is expressed in the k/v BlockSpec index maps
(q head h reads kv head h // group_size) — no repeated kv in HBM.
Block shapes default to 128x128 tiles (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, bq: int, bk: int, nk: int,
            scale: float):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :, :]                               # (bq, hd)
    k = k_ref[0, 0, :, :]                               # (bk, hd)
    v = v_ref[0, 0, :, :]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # (bq, bk)

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > (qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                              # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                      # (bq, 1)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bq, hd)
    acc_new = acc_prev * corr + pv

    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(j == nk - 1)
    def _flush():
        o_ref[0, 0, :, :] = (acc_new / jnp.maximum(l_new, 1e-30)
                             ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Hq, S, hd); k, v: (B, Hkv, S, hd).  Returns (B, Hq, S, hd).

    S must divide by the block sizes (the launcher pads); GQA via
    index-map head folding.  interpret=True validates on CPU; on TPU the
    same call lowers to an MXU kernel with VMEM-resident accumulators.
    """
    B, Hq, S, hd = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    scale = 1.0 / (hd ** 0.5)

    grid = (B, Hq, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, window=window, bq=bq,
                          bk=bk, nk=nk, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max m
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out
