"""Pallas TPU kernel: L1 proximal operator (soft threshold).

AdaSplit drives masks / split activations sparse with an L1 term; the
proximal form ``sign(x) * max(|x| - t, 0)`` is the fused update applied
to masks after each server step and to activation payloads before
transmission (Table 6).  Elementwise over VMEM tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, o_ref, *, threshold: float):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = (jnp.sign(x) * jnp.maximum(jnp.abs(x) - threshold, 0.0)
                  ).astype(o_ref.dtype)


def soft_threshold_2d(x, threshold: float, *, block: tuple = (256, 256),
                      interpret: bool = False):
    """x: (M, N) -> soft-thresholded, tiled (bm, bn) blocks in VMEM."""
    M, N = x.shape
    bm, bn = min(block[0], M), min(block[1], N)
    Mp = ((M + bm - 1) // bm) * bm
    Np = ((N + bn - 1) // bn) * bn
    xp = jnp.pad(x, ((0, Mp - M), (0, Np - N)))
    out = pl.pallas_call(
        functools.partial(_kernel, threshold=float(threshold)),
        grid=(Mp // bm, Np // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        interpret=interpret,
    )(xp)
    return out[:M, :N]


def soft_threshold(x, threshold: float, *, interpret: bool = False):
    """Any-rank wrapper: flattens to 2D tiles."""
    shape = x.shape
    n = x.size
    # fold into (rows, 256) panels
    cols = 256 if n >= 256 else n
    rows = (n + cols - 1) // cols
    flat = jnp.pad(x.reshape(-1), (0, rows * cols - n))
    out = soft_threshold_2d(flat.reshape(rows, cols), threshold,
                            interpret=interpret)
    return out.reshape(-1)[:n].reshape(shape)
