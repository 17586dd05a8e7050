"""Pallas TPU kernel: fused masked-Adam server update (AdaSplit eq. 7).

The server update ``M^s <- M^s - alpha * m_i * Adam(grad)`` touches four
HBM-resident tensors per param (p, g, mu, nu) plus the client mask; the
fused kernel reads each once and writes (p, mu, nu) once — 1 pass
instead of the ~3 the unfused XLA lowering makes.  Bias-correction
scalars arrive via scalar-prefetch (SMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(sc_ref, p_ref, g_ref, mu_ref, nu_ref, mask_ref,
            p_out, mu_out, nu_out, *, lr, b1, b2, eps):
    b1t = sc_ref[0]          # 1 - b1^t
    b2t = sc_ref[1]          # 1 - b2^t
    g = g_ref[...].astype(jnp.float32)
    if mask_ref is not None:
        g = g * mask_ref[...].astype(jnp.float32)
    mu = b1 * mu_ref[...] + (1 - b1) * g
    nu = b2 * nu_ref[...] + (1 - b2) * g * g
    mhat = mu / b1t
    nhat = nu / b2t
    p = p_ref[...].astype(jnp.float32) - lr * mhat / (jnp.sqrt(nhat) + eps)
    p_out[...] = p.astype(p_out.dtype)
    mu_out[...] = mu
    nu_out[...] = nu


def masked_adam_2d(p, g, mu, nu, mask, *, lr, b1, b2, eps, b1t, b2t,
                   block=(256, 256), interpret: bool = False):
    """All operands (M, N); b1t/b2t are traced scalars (1 - beta^t).

    mask=None lowers the no-mask variant (plain fused Adam): the fifth
    operand is dropped entirely, so no all-ones tensor is streamed
    through HBM just to multiply by 1.
    """
    M, N = p.shape
    bm, bn = min(block[0], M), min(block[1], N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    grid = (M // bm, N // bn)
    # index maps receive the scalar-prefetch ref as a trailing arg
    spec = pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j))
    scalars = jnp.stack([jnp.asarray(b1t, jnp.float32),
                         jnp.asarray(b2t, jnp.float32)])
    n_in = 4 if mask is None else 5
    kernel = functools.partial(_kernel, lr=float(lr), b1=float(b1),
                               b2=float(b2), eps=float(eps))
    if mask is None:
        kernel = functools.partial(_nomask_kernel, kernel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=[spec] * n_in, out_specs=[spec] * 3)
    operands = (p, g, mu, nu) if mask is None else (p, g, mu, nu, mask)
    new_p, new_mu, new_nu = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((M, N), p.dtype),
                   jax.ShapeDtypeStruct((M, N), jnp.float32),
                   jax.ShapeDtypeStruct((M, N), jnp.float32)],
        interpret=interpret,
        name="masked_adam",
    )(scalars, *operands)
    return new_p, new_mu, new_nu


def _nomask_kernel(kernel, sc_ref, p_ref, g_ref, mu_ref, nu_ref,
                   p_out, mu_out, nu_out):
    kernel(sc_ref, p_ref, g_ref, mu_ref, nu_ref, None,
           p_out, mu_out, nu_out)


def masked_adam(p, g, mu, nu, mask, *, lr, b1=0.9, b2=0.999, eps=1e-8,
                step=1, interpret: bool = False):
    """Any-rank wrapper (reshapes to 2D panels; pads to tile multiples).

    mask may be None (plain fused Adam, 4 streamed inputs)."""
    shape = p.shape
    n = p.size
    cols = 256 if n >= 256 else n
    rows = (n + cols - 1) // cols
    pad = rows * cols - n

    def panel(x, fill=0.0):
        return jnp.pad(x.reshape(-1), (0, pad),
                       constant_values=fill).reshape(rows, cols)

    stepf = jnp.asarray(step, jnp.float32)
    b1t = 1.0 - b1 ** stepf
    b2t = 1.0 - b2 ** stepf
    bm = min(256, rows)
    # pad rows to a multiple of bm
    rpad = (bm - rows % bm) % bm
    ops = (p, g, mu.astype(jnp.float32), nu.astype(jnp.float32)) \
        + (() if mask is None else (mask,))
    args = [jnp.pad(panel(x), ((0, rpad), (0, 0))) for x in ops]
    if mask is None:
        args.append(None)
    new_p, new_mu, new_nu = masked_adam_2d(
        *args, lr=lr, b1=b1, b2=b2, eps=eps, b1t=b1t, b2t=b2t,
        block=(bm, cols), interpret=interpret)
    unpanel = lambda x: x[:rows].reshape(-1)[:n].reshape(shape)
    return unpanel(new_p), unpanel(new_mu), unpanel(new_nu)


def fused_adam_update(params, grads, state, *, lr, b1=0.9, b2=0.999,
                      eps=1e-8, mask=None, interpret: bool = False):
    """Drop-in ``optim.adam.adam_update`` twin running every leaf
    through the fused kernel (one HBM pass per leaf instead of ~3).

    ``state`` is an ``adam_init`` dict; ``mask`` an optional pytree of
    multiplicative gradient masks (defaults to all-ones — plain Adam).
    The kernel lowers natively, which only a TPU backend compiles
    (``fused_mask_adam`` / ``fused_server_adam`` in core/adasplit.py
    refuse it elsewhere); ``interpret=True`` runs the same kernel
    through the Pallas interpreter for CPU validation.
    """
    step = state["step"] + 1
    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_mu = treedef.flatten_up_to(state["mu"])
    flat_nu = treedef.flatten_up_to(state["nu"])
    flat_m = treedef.flatten_up_to(mask) if mask is not None \
        else [None] * len(flat_p)
    out = []
    for p, g, mu, nu, m in zip(flat_p, flat_g, flat_mu, flat_nu, flat_m):
        out.append(masked_adam(p, g, mu, nu, m, lr=lr, b1=b1, b2=b2,
                               eps=eps, step=step, interpret=interpret))
    new_p = treedef.unflatten([o[0] for o in out])
    new_mu = treedef.unflatten([o[1] for o in out])
    new_nu = treedef.unflatten([o[2] for o in out])
    return new_p, {"mu": new_mu, "nu": new_nu, "step": step}
