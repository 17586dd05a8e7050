"""Blocked batched-GEMM client convolution: im2col patches x per-client
filter panels.

AdaSplit's hot path runs the SAME KxK "same" conv with DIFFERENT
per-client weights across a stacked client axis.  ``jax.vmap`` of
``lax.conv_general_dilated`` lowers that to a feature-group convolution
that XLA:CPU executes group-serially: the forward pays ~C x one-client
latency and the transposed backward is catastrophically worse (~70x
slower than the GEMM form measured at C=32 on the 2-core CPU box), so
N-client rounds stayed conv-latency-bound no matter how much control
plane the round scan removed.

Reformulated via im2col, the whole stacked conv is ONE blocked batched
GEMM

    (C, B*H*W, K*K*Cin) @ (C, K*K*Cin, Cout)

with two lowerings:

* ``method="einsum"`` — pure XLA: patches built from K*K shifted
  slices, contraction by ``jnp.matmul``.  This lowers to a batched
  ``dot_general`` on EVERY backend, and because a dot_general's
  transpose is another dot_general, forward AND backward are batched
  GEMMs.  This is the autodiff primal used by training.
* ``method="pallas"`` — the same contraction as a TPU-native
  ``pallas_call`` (one (bm, K*K*Cin) patch panel x (K*K*Cin, Cout)
  filter panel per grid step, f32 MXU accumulation).  It lowers
  natively (``interpret=False``, the default, which only a TPU
  backend can compile); CPU parity tests pass ``interpret=True``
  explicitly.  A custom VJP routes its backward through the
  einsum-form batched GEMMs.

``method="conv"`` keeps the vmapped ``lax.conv_general_dilated``
grouped lowering as the differential-test reference.  All methods
accept unstacked weights (K, K, Cin, Cout) — a single conv, still a
GEMM — or stacked (C, K, K, Cin, Cout) with inputs (C, B, H, W, Cin);
under a client ``vmap`` the unstacked form is traced and the batching
transform produces exactly the stacked contraction.

The leading C is whatever client axis reaches this kernel: the full
cohort on one device, or — under ``shard_clients`` cohort sharding —
the ``shard_map``-local C/ndev slice, where each device runs its own
(C/ndev, B*H*W, K*K*Cin) panel batch.  Per-client results are
independent (the GEMM's K-reduction runs per panel), so sharding the
panel batch never changes the contraction — only backend blocking
choices at different batch widths can perturb the last float bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def default_method() -> str:
    """``method=None``'s resolution: the Pallas kernel where it lowers
    natively (TPU), the einsum GEMM elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "einsum"


# ---------------------------------------------------------------------------
# im2col ("same" padding, stride 1, odd K)
# ---------------------------------------------------------------------------


def im2col(x, k: int):
    """(..., H, W, Cin) -> (..., H, W, K*K*Cin) patch tensor.

    K*K shifted HxW slices of the zero-padded input, concatenated along
    the channel axis in (ki, kj, cin) row-major order — the same order
    ``w.reshape(..., K*K*Cin, Cout)`` flattens the filter, so the conv
    is exactly ``patches @ panel``.  Concatenation of whole slices is
    the fastest patch builder XLA:CPU lowers (measured against stack /
    gather / conv_general_dilated_patches forms).
    """
    assert k % 2 == 1, k
    h, w = x.shape[-3], x.shape[-2]
    pad = k // 2
    cfg = [(0, 0)] * (x.ndim - 3) + [(pad, pad), (pad, pad), (0, 0)]
    with jax.named_scope("im2col"):   # names its ops, and their transpose
        xp = jnp.pad(x, cfg)
        cols = [jax.lax.slice_in_dim(
            jax.lax.slice_in_dim(xp, i, i + h, axis=-3), j, j + w, axis=-2)
            for i in range(k) for j in range(k)]
        return jnp.concatenate(cols, axis=-1)


def _panels(x, w):
    """(patches_2d, filter_panels, out_shape): the GEMM operands.

    patches: (lead..., M, K*K*Cin) with M = prod of the non-client,
    non-channel axes; panels: (lead..., K*K*Cin, Cout)."""
    lead = w.shape[:-4]
    assert x.shape[:len(lead)] == lead, (x.shape, w.shape)
    k, cout = w.shape[-4], w.shape[-1]
    kd = k * k * w.shape[-2]
    patches = im2col(x, k).reshape(lead + (-1, kd))
    panels = w.reshape(lead + (kd, cout))
    return patches, panels, x.shape[:-1] + (cout,)


# ---------------------------------------------------------------------------
# Pallas blocked batched GEMM
# ---------------------------------------------------------------------------


def _gemm_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jnp.dot(a_ref[0], b_ref[0],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)[None]


def panel_gemm_2d(a, b, *, block_m: int = 128, interpret: bool = False):
    """(C, M, K) @ (C, K, N) -> (C, M, N), one (1, bm, K) x (1, K, N)
    MXU tile per grid step.  M/K/N must already be padded to tile
    multiples (M % bm == 0; K, N % 128 == 0 for the native lowering)."""
    C, M, K = a.shape
    N = b.shape[-1]
    bm = min(block_m, M)
    assert M % bm == 0, (M, bm)
    grid = (C, M // bm)
    return pl.pallas_call(
        _gemm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bm, K), lambda c, m: (c, m, 0)),
                  pl.BlockSpec((1, K, N), lambda c, m: (c, 0, 0))],
        out_specs=pl.BlockSpec((1, bm, N), lambda c, m: (c, m, 0)),
        out_shape=jax.ShapeDtypeStruct((C, M, N), a.dtype),
        interpret=interpret,
        name="panel_gemm",
    )(a, b)


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _panel_gemm_fwd(a, b, interpret):
    """Tile-padded pallas dispatch: a (C, M, K) @ b (C, K, N)."""
    M, N = a.shape[1], b.shape[2]
    ap = _pad_to(_pad_to(a, 2, 128), 1, 128)
    bp = _pad_to(_pad_to(b, 1, 128), 2, 128)
    out = panel_gemm_2d(ap, bp, interpret=interpret)
    return out[:, :M, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def panel_gemm(a, b, interpret=False):
    """Batched GEMM through the Pallas kernel; backward through the
    einsum-form batched GEMMs (a dot_general's transpose is another
    dot_general — no grouped lowering anywhere)."""
    return _panel_gemm_fwd(a, b, interpret)


def _panel_gemm_vjp_fwd(a, b, interpret):
    return _panel_gemm_fwd(a, b, interpret), (a, b)


def _panel_gemm_vjp_bwd(interpret, res, g):
    a, b = res
    da = jnp.einsum("cmn,ckn->cmk", g, b).astype(a.dtype)
    db = jnp.einsum("cmk,cmn->ckn", a, g).astype(b.dtype)
    return da, db


panel_gemm.defvjp(_panel_gemm_vjp_fwd, _panel_gemm_vjp_bwd)


# ---------------------------------------------------------------------------
# fused bias+ReLU epilogue (ROADMAP next-step)
# ---------------------------------------------------------------------------


def _gemm_bias_relu_kernel(a_ref, b_ref, bias_ref, o_ref):
    z = jnp.dot(a_ref[0], b_ref[0], preferred_element_type=jnp.float32)
    z = z + bias_ref[0].astype(jnp.float32)          # (1, N) row
    o_ref[...] = jnp.maximum(z, 0).astype(o_ref.dtype)[None]


def panel_gemm_bias_relu_2d(a, b, bias, *, block_m: int = 128,
                            interpret: bool = False):
    """relu((C, M, K) @ (C, K, N) + bias (C, N)) with the bias add and
    ReLU fused into the GEMM epilogue — the accumulator tile is
    rectified in registers before the HBM writeback, instead of a
    separate elementwise pass re-reading the (C, M, N) output.

    The bias travels as (C, 1, N) so its block's last two dims, (1, N),
    equal the array's: Mosaic refuses a (1, N) block over (C, N)
    unless C is a multiple of 8."""
    C, M, K = a.shape
    N = b.shape[-1]
    bm = min(block_m, M)
    assert M % bm == 0, (M, bm)
    grid = (C, M // bm)
    return pl.pallas_call(
        _gemm_bias_relu_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bm, K), lambda c, m: (c, m, 0)),
                  pl.BlockSpec((1, K, N), lambda c, m: (c, 0, 0)),
                  pl.BlockSpec((1, 1, N), lambda c, m: (c, 0, 0))],
        out_specs=pl.BlockSpec((1, bm, N), lambda c, m: (c, m, 0)),
        out_shape=jax.ShapeDtypeStruct((C, M, N), a.dtype),
        interpret=interpret,
        name="panel_gemm_bias_relu",
    )(a, b, bias.reshape(C, 1, N))


def _panel_gemm_fused_fwd(a, b, bias, interpret):
    M, N = a.shape[1], b.shape[2]
    ap = _pad_to(_pad_to(a, 2, 128), 1, 128)
    bp = _pad_to(_pad_to(b, 1, 128), 2, 128)
    biasp = _pad_to(bias, 1, 128)
    out = panel_gemm_bias_relu_2d(ap, bp, biasp, interpret=interpret)
    return out[:, :M, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def panel_gemm_fused(a, b, bias, interpret=False):
    """``relu(panel_gemm(a, b) + bias[:, None, :])`` through the fused
    Pallas epilogue kernel; backward unchanged — routed through the
    same einsum-form batched GEMMs as :func:`panel_gemm`, with the ReLU
    mask recovered from the saved output (``out > 0`` ⟺ pre-activation
    > 0, the identical subgradient-at-0 convention as ``jax.nn.relu``).
    """
    return _panel_gemm_fused_fwd(a, b, bias, interpret)


def _panel_gemm_fused_vjp_fwd(a, b, bias, interpret):
    out = _panel_gemm_fused_fwd(a, b, bias, interpret)
    return out, (a, b, bias, out)


def _panel_gemm_fused_vjp_bwd(interpret, res, g):
    a, b, bias, out = res
    dz = jnp.where(out > 0, g, 0)
    da = jnp.einsum("cmn,ckn->cmk", dz, b).astype(a.dtype)
    db = jnp.einsum("cmk,cmn->ckn", a, dz).astype(b.dtype)
    dbias = jnp.sum(dz, axis=1).astype(bias.dtype)
    return da, db, dbias


panel_gemm_fused.defvjp(_panel_gemm_fused_vjp_fwd,
                        _panel_gemm_fused_vjp_bwd)


# ---------------------------------------------------------------------------
# public conv entry point
# ---------------------------------------------------------------------------


def broadcast_bias(bias):
    """A conv bias shaped for NHWC broadcast: stacked (C, Cout) ->
    (C, 1, 1, 1, Cout); unstacked (Cout,) unchanged.  The ONE
    definition of the epilogue's broadcast — shared by the fused and
    unfused paths (and lenet._conv_block) so they stay bit-identical.
    """
    if bias.ndim > 1:
        return bias.reshape(bias.shape[:-1] + (1, 1, 1) + bias.shape[-1:])
    return bias


def client_conv(x, w, *, method: str | None = None, bias=None,
                fused_epilogue: bool = False, interpret: bool = False):
    """Stacked-client KxK "same" conv, client axis optional.

    x: (C, B, H, W, Cin) with w (C, K, K, Cin, Cout), or unstacked
    (..., H, W, Cin) with w (K, K, Cin, Cout).  method: "einsum"
    (autodiff primal, batched GEMM on every backend), "pallas"
    (TPU-native kernel, custom VJP), "conv" (vmapped grouped-conv
    reference), or None = backend default.

    ``fused_epilogue=True`` (requires ``bias``: (C, Cout) stacked or
    (Cout,)) returns ``relu(conv + bias)`` with the epilogue fused into
    the Pallas GEMM's writeback on the "pallas" path; the "einsum" /
    "conv" paths apply the identical ``relu(. + bias)`` epilogue as
    plain XLA ops (same float ops in the same order as the unfused
    caller-side bias+ReLU, so CPU training paths are bit-unchanged).

    ``interpret=True`` runs the "pallas" kernels through the Pallas
    interpreter (CPU parity tests); the XLA methods ignore it.
    """
    if method is None:
        method = default_method()
    assert (bias is not None) == fused_epilogue, (fused_epilogue, bias)
    if method == "conv":
        y = _conv_reference(x, w)
        if fused_epilogue:
            y = jax.nn.relu(y + broadcast_bias(bias).astype(y.dtype))
        return y
    patches, panels, out_shape = _panels(x, w)
    if method == "einsum":
        y = jnp.matmul(patches, panels).reshape(out_shape)
        if fused_epilogue:
            # identical op order to the caller-side epilogue (reshape,
            # add, relu) so training graphs are BIT-unchanged on the
            # einsum path; XLA fuses the elementwise tail into the GEMM
            # consumer either way
            y = jax.nn.relu(y + broadcast_bias(bias).astype(y.dtype))
        return y
    assert method == "pallas", method
    if fused_epilogue:
        bias = bias.astype(x.dtype)
        if w.ndim == 4:                  # unstacked: batch of one panel
            out = panel_gemm_fused(patches[None], panels[None],
                                   bias[None], interpret)[0]
        else:
            out = panel_gemm_fused(patches, panels, bias, interpret)
    elif w.ndim == 4:
        out = panel_gemm(patches[None], panels[None], interpret)[0]
    else:
        out = panel_gemm(patches, panels, interpret)
    return out.reshape(out_shape)


def _conv_reference(x, w):
    """The seed lowering: per-client lax convs (grouped under vmap).
    Delegates to the ref.py oracle; only adds the leading-axis
    flattening for shared-weight inputs with extra batch axes."""
    from repro.kernels.ref import client_conv_ref
    if w.ndim == 4 and x.ndim > 4:       # extra leading axes -> batch
        y = client_conv_ref(x.reshape((-1,) + x.shape[-3:]), w)
        return y.reshape(x.shape[:-1] + (w.shape[-1],))
    return client_conv_ref(x, w)


# ---------------------------------------------------------------------------
# stacked projection head (the LM client tower's analogue)
# ---------------------------------------------------------------------------


def client_proj(proj, h):
    """Client-axis-aware 2-layer projection head.

    h: (..., M, D) features; proj leaves (..., D, H') / (..., H') with
    the same leading client axes as ``h`` (or none, under a cohort
    vmap).  ``jnp.matmul`` broadcasts the leading axes, so stacked
    params run as ONE batched GEMM per layer — the dense analogue of
    :func:`client_conv` — instead of C serial dispatches.
    """
    def bias(b):
        return b.reshape(b.shape[:-1] + (1,) + b.shape[-1:])
    z = jax.nn.relu(jnp.matmul(h, proj["w1"]) + bias(proj["b1"]))
    return jnp.matmul(z, proj["w2"])
