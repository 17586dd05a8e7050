"""Pallas TPU kernel: supervised NT-Xent statistics (AdaSplit eq. 5).

The paper's per-iteration client hot-spot is the (B, B) similarity
matrix over projected activations.  The kernel tiles rows into VMEM
blocks of ``block_rows`` and computes, per row i:

    lse_i     = logsumexp_{j != i} (q_i . q_j / tau)
    pos_sum_i = sum_{j: y_j == y_i, j != i} (q_i . q_j / tau)
    pos_cnt_i = |{j: y_j == y_i, j != i}|

from which the loss is ``sum(cnt * lse - pos_sum) / sum(cnt)``
(see ``repro.kernels.ref.ntxent_loss_from_stats``).

Layout: q is (B, D) with D the projection dim (<= a few hundred), so the
whole q matrix fits VMEM alongside one row block; the row block x full-q
matmul runs on the MXU.  Row-block size is 128-aligned for the lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_blk_ref, rows_ref, q_all_ref, labels_ref, lse_ref,
            pos_sum_ref, pos_cnt_ref, *, tau: float, n_valid: int):
    q_blk = q_blk_ref[...].astype(jnp.float32)          # (bm, D)
    q_all = q_all_ref[...].astype(jnp.float32)          # (B, D)
    rows = rows_ref[...]                                # (bm, 1) global ids
    labels = labels_ref[...]                            # (B, 1)

    sim = jax.lax.dot_general(
        q_blk, q_all, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) / tau       # (bm, B)

    B = q_all.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
    diag = rows == cols                                 # (bm, B)
    col_valid = cols < n_valid                          # padded rows masked
    neg_inf = jnp.float32(-1e30)

    sim_m = jnp.where(diag | ~col_valid, neg_inf, sim)
    m = jnp.max(sim_m, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(sim_m - m), axis=-1, keepdims=True)) + m

    row_lab = jnp.take_along_axis(
        jnp.broadcast_to(labels.T, (rows.shape[0], B)),
        jnp.clip(rows, 0, B - 1), axis=1)               # (bm, 1)
    pos = (labels.T == row_lab) & ~diag & col_valid     # (bm, B)
    pos_sum = jnp.sum(jnp.where(pos, sim, 0.0), axis=-1, keepdims=True)
    pos_cnt = jnp.sum(pos.astype(jnp.float32), axis=-1, keepdims=True)

    lse_ref[...] = lse
    pos_sum_ref[...] = pos_sum
    pos_cnt_ref[...] = pos_cnt


def ntxent_stats(q, labels, tau: float = 0.07, *, block_rows: int = 128,
                 interpret: bool = False):
    """Returns (lse, pos_sum, pos_cnt), each (B,) float32.

    q: (B, D); labels: (B,) int32.  B is padded up to a multiple of
    ``block_rows`` internally; padded rows are excluded everywhere.
    """
    B, D = q.shape
    bm = min(block_rows, max(8, B))
    Bp = ((B + bm - 1) // bm) * bm
    qp = jnp.pad(q.astype(jnp.float32), ((0, Bp - B), (0, 0)))
    lp = jnp.pad(labels.astype(jnp.int32), (0, Bp - B),
                 constant_values=-1)[:, None]            # (Bp, 1)
    rows = jnp.arange(Bp, dtype=jnp.int32)[:, None]      # (Bp, 1)

    grid = (Bp // bm,)
    out_shape = [jax.ShapeDtypeStruct((Bp, 1), jnp.float32)] * 3
    lse, pos_sum, pos_cnt = pl.pallas_call(
        functools.partial(_kernel, tau=tau, n_valid=B),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, D), lambda i: (i, 0)),     # q row block
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),     # global row ids
            pl.BlockSpec((Bp, D), lambda i: (0, 0)),     # full q
            pl.BlockSpec((Bp, 1), lambda i: (0, 0)),     # labels
        ],
        out_specs=[pl.BlockSpec((bm, 1), lambda i: (i, 0))] * 3,
        out_shape=out_shape,
        interpret=interpret,
    )(qp, rows, qp, lp)
    return lse[:B, 0], pos_sum[:B, 0], pos_cnt[:B, 0]


def ntxent_loss(q, labels, tau: float = 0.07, *, normalize: bool = True,
                interpret: bool = False):
    """Kernel-backed supervised NT-Xent loss (mean over positive pairs)."""
    qf = q.astype(jnp.float32)
    if normalize:
        qf = qf / (jnp.linalg.norm(qf, axis=-1, keepdims=True) + 1e-8)
    lse, pos_sum, pos_cnt = ntxent_stats(qf, labels, tau,
                                         interpret=interpret)
    n_pos = jnp.maximum(jnp.sum(pos_cnt), 1.0)
    return jnp.sum(pos_cnt * lse - pos_sum) / n_pos
