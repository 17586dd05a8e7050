"""jit'd public wrappers for the Pallas kernels.

Each op lowers its kernel natively unless the caller passes
``interpret=True`` (the Pallas interpreter, which CPU tests use to check
the kernel's math); a native lowering needs a TPU backend and fails
loudly elsewhere — nothing falls back behind the caller's back.  Every
op is shape-polymorphic via padding in the kernel modules.  The pure-jnp
oracles live in ``repro.kernels.ref`` and tests assert allclose across
shape/dtype sweeps.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import client_conv as _cc
from repro.kernels import flash_attention as _fa
from repro.kernels import masked_adam as _ma
from repro.kernels import ntxent as _nt
from repro.kernels import soft_threshold as _st


@functools.partial(jax.jit, static_argnames=("tau", "normalize",
                                             "interpret"))
def ntxent_loss(q, labels, tau: float = 0.07, normalize: bool = True,
                interpret: bool = False):
    return _nt.ntxent_loss(q, labels, tau, normalize=normalize,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Hq, S, hd); k/v: (B, Hkv, S, hd)."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("threshold", "interpret"))
def soft_threshold(x, threshold: float, interpret: bool = False):
    return _st.soft_threshold(x, threshold, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("method", "interpret"))
def client_conv(x, w, method: str = None, interpret: bool = False):
    """Stacked-client conv as one batched GEMM.  x (C, B, H, W, Cin),
    w (C, K, K, Cin, Cout) (client axis optional on both); method None
    = backend default (pallas on TPU, einsum elsewhere)."""
    return _cc.client_conv(x, w, method=method, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps",
                                             "interpret"))
def masked_adam(p, g, mu, nu, mask, step, lr: float = 1e-3,
                b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                interpret: bool = False):
    return _ma.masked_adam(p, g, mu, nu, mask, lr=lr, b1=b1, b2=b2,
                           eps=eps, step=step, interpret=interpret)


def compiled_kernels(compiled_text: str) -> set:
    """Names of the Pallas kernels in a compiled TPU program's HLO text
    (``compiled.as_text()``): each native kernel is a ``tpu_custom_call``
    whose op name runs ``.../<kernel name>/pallas_call``, the name
    wrapped by any transform applied to it (``jvp(panel_gemm)``,
    ``vmap(masked_adam)``).  Returns every op-name scope of those calls,
    unwrapped."""
    names = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op = line.split('op_name="', 1)[-1].split('"', 1)[0]
        for part in op.split("/"):
            while part.endswith(")") and "(" in part:
                part = part[part.index("(") + 1:-1]
            names.add(part)
    return names
