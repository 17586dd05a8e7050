"""Batched-GEMM client conv: differential tests vs the grouped
``lax.conv_general_dilated`` reference (forward AND gradients, fp32
tolerance 1e-5), Pallas interpret-mode parity, and the model-level
``batched_conv`` wiring (split indices, odd image sizes, per-client vs
per-example gates)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import client_conv as cc
from repro.kernels import ops, ref
from repro.models import lenet

RNG = np.random.default_rng(11)
CFG = get_config("lenet-cifar")


def _xw(C, B, H, W, cin, cout, k=5):
    x = jnp.asarray(RNG.normal(size=(C, B, H, W, cin)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(C, k, k, cin, cout)) / (k * k * cin),
                    jnp.float32)
    return x, w


def _close(a, b, tol=1e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


# ---------------------------------------------------------------------------
# kernel-level differential: einsum / pallas vs grouped-conv oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("C,B,H,W,cin,cout", [
    (1, 2, 8, 8, 3, 6), (3, 2, 9, 9, 3, 4),     # odd spatial
    (4, 1, 8, 8, 6, 16), (2, 3, 7, 11, 4, 8),   # non-square, odd
])
def test_forward_matches_grouped_reference(C, B, H, W, cin, cout):
    x, w = _xw(C, B, H, W, cin, cout)
    want = ref.client_conv_ref(x, w)
    _close(cc.client_conv(x, w, method="einsum"), want)
    _close(ops.client_conv(x, w, method="einsum"), want)


def test_forward_unstacked_matches_plain_conv():
    x, w = _xw(1, 4, 8, 8, 3, 6)
    x, w = x[0], w[0]
    want = ref.client_conv_ref(x, w)
    _close(cc.client_conv(x, w, method="einsum"), want)
    _close(cc.client_conv(x, w, method="pallas", interpret=True), want)


def test_vmap_of_unstacked_equals_stacked():
    """The wiring contract: a per-client vmap of the unstacked einsum
    form IS the stacked batched GEMM."""
    x, w = _xw(5, 2, 8, 8, 3, 6)
    got = jax.vmap(lambda x, w: cc.client_conv(x, w, method="einsum"))(x, w)
    _close(got, cc.client_conv(x, w, method="einsum"), tol=1e-6)


@pytest.mark.parametrize("method", ["einsum", "pallas"])
def test_grads_match_grouped_reference(method):
    x, w = _xw(3, 2, 8, 8, 3, 6)

    def loss(m):
        return lambda w, x: jnp.mean(cc.client_conv(
            x, w, method=m, interpret=True) ** 2)

    want_w, want_x = jax.grad(loss("conv"), argnums=(0, 1))(w, x)
    got_w, got_x = jax.grad(loss(method), argnums=(0, 1))(w, x)
    _close(got_w, want_w)
    _close(got_x, want_x)


def test_pallas_interpret_matches_einsum():
    """Interpret-mode parity: the TPU kernel's math == the XLA primal
    (forward exactly, VJP through the custom rule)."""
    x, w = _xw(2, 2, 9, 9, 4, 8)
    f_e = cc.client_conv(x, w, method="einsum")
    f_p = cc.client_conv(x, w, method="pallas", interpret=True)
    # the interpreter's 128-padded dot and XLA's einsum sum the same
    # products in different orders, so the last float bit may differ
    # (measured max abs diff 9e-8 under jax 0.9); 1e-6 is a few f32
    # ULPs at the outputs' unit scale
    _close(f_p, f_e, tol=1e-6)

    def loss(m):
        return lambda w: jnp.mean(cc.client_conv(
            x, w, method=m, interpret=True) ** 2)
    _close(jax.grad(loss("pallas"))(w), jax.grad(loss("einsum"))(w),
           tol=1e-6)


def test_panel_gemm_pads_ragged_tiles():
    """M/K/N far from the 128 tile: padding must be invisible."""
    a = jnp.asarray(RNG.normal(size=(2, 37, 75)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(2, 75, 6)), jnp.float32)
    _close(cc.panel_gemm(a, b, True), jnp.matmul(a, b), tol=1e-5)


# ---------------------------------------------------------------------------
# model-level wiring: split indices, odd image size, gates
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mu", [0.2, 0.45, 0.65])   # split at 1 / 2 / 3
def test_client_forward_across_split_indices(mu):
    cfg = dataclasses.replace(CFG, mu=mu)
    s = lenet.split_index(cfg)
    assert s == max(1, int(round(mu * len(cfg.conv_channels))))
    C, B = 3, 2
    cps = [lenet.init_client_params(cfg, jax.random.PRNGKey(i))
           for i in range(C)]
    stacked = jax.tree.map(lambda *x: jnp.stack(x), *cps)
    x = jnp.asarray(RNG.normal(size=(C, B, cfg.image_size,
                                     cfg.image_size, 3)), jnp.float32)
    want = jnp.stack([lenet.client_forward(cfg, cp, x[i])
                      for i, cp in enumerate(cps)])
    # stacked params, no vmap: the client-axis-aware _conv_block
    got = lenet.client_forward(cfg, stacked, x, batched_conv=True)
    _close(got, want)
    # vmapped batched_conv path (the client_step lowering)
    got_v = jax.vmap(lambda cp, x: lenet.client_forward(
        cfg, cp, x, batched_conv=True))(stacked, x)
    _close(got_v, want)


@pytest.mark.parametrize("image_size", [21, 30])
def test_client_forward_odd_image_size(image_size):
    cfg = dataclasses.replace(CFG, image_size=image_size)
    cp = lenet.init_client_params(cfg, jax.random.PRNGKey(0))
    x = jnp.asarray(RNG.normal(size=(2, image_size, image_size, 3)),
                    jnp.float32)
    want = lenet.client_forward(cfg, cp, x)
    _close(lenet.client_forward(cfg, cp, x, batched_conv=True), want)


@pytest.mark.parametrize("per_example", [False, True],
                         ids=["per_client", "per_example"])
def test_server_forward_gates_batched_conv(per_example):
    """Per-client (U,) vs per-example (B, U) gates: the GEMM conv path
    must compose with both gate forms exactly like the reference."""
    from repro.core import masks as masks_mod
    cfg = CFG
    B = 4
    sp = lenet.init_server_params(cfg, jax.random.PRNGKey(1))
    masks = masks_mod.init_lenet_unit_masks(cfg, 3)
    gates = masks_mod.lenet_gates_for_client(
        jax.tree.map(lambda l: l * jnp.asarray(
            RNG.uniform(0.2, 1.0, l.shape), l.dtype), masks), 1)
    if per_example:
        gates = jax.tree.map(
            lambda l: jnp.tile(l[None], (B,) + (1,) * l.ndim) *
            jnp.linspace(0.5, 1.0, B).reshape((B,) + (1,) * l.ndim), gates)
    acts = jnp.asarray(RNG.normal(size=(B, 16, 16,
                                        cfg.conv_channels[0])), jnp.float32)
    want, _ = lenet.server_forward(cfg, sp, acts, gates=gates)
    got, _ = lenet.server_forward(cfg, sp, acts, gates=gates,
                                  batched_conv=True)
    _close(got, want)


def test_conv_block_stacked_gates_broadcast():
    """Client-axis-aware gating: stacked (C, U) and (C, B, U) gates on
    stacked 5D activations == per-client unstacked blocks."""
    C, B, cin, cout = 3, 2, 4, 8
    x = jnp.asarray(RNG.normal(size=(C, B, 8, 8, cin)), jnp.float32)
    p = {"w": jnp.asarray(RNG.normal(size=(C, 5, 5, cin, cout)) / 100,
                          jnp.float32),
         "b": jnp.asarray(RNG.normal(size=(C, cout)), jnp.float32)}
    for gate in (jnp.asarray(RNG.uniform(0.2, 1, (C, cout)), jnp.float32),
                 jnp.asarray(RNG.uniform(0.2, 1, (C, B, cout)),
                             jnp.float32)):
        got = lenet._conv_block(p, x, gate=gate, batched_conv=True)
        want = jnp.stack([
            lenet._conv_block(jax.tree.map(lambda l: l[i], p), x[i],
                              gate=gate[i])
            for i in range(C)])
        _close(got, want)


def test_client_proj_stacked_equals_vmap():
    C, B, D = 4, 3, 16
    proj = {"w1": jnp.asarray(RNG.normal(size=(C, D, 8)), jnp.float32),
            "b1": jnp.asarray(RNG.normal(size=(C, 8)), jnp.float32),
            "w2": jnp.asarray(RNG.normal(size=(C, 8, 5)), jnp.float32)}
    h = jnp.asarray(RNG.normal(size=(C, B, D)), jnp.float32)
    got = cc.client_proj(proj, h)
    want = jax.vmap(cc.client_proj)(proj, h)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# fused bias+ReLU epilogue (satellite): interpret-mode parity
# ---------------------------------------------------------------------------


def _bias_relu_ref(x, w, b):
    y = ref.client_conv_ref(x, w)
    bb = b.reshape(b.shape[:-1] + (1,) * (y.ndim - b.ndim) + b.shape[-1:]) \
        if b.ndim > 1 else b
    return jax.nn.relu(y + bb)


@pytest.mark.parametrize("method", ["einsum", "pallas", "conv"])
def test_fused_epilogue_forward_matches_reference(method):
    """relu(conv + bias) through the fused epilogue == the grouped-conv
    reference with caller-side bias+ReLU, stacked and unstacked."""
    x, w = _xw(3, 2, 8, 8, 3, 6)
    b = jnp.asarray(RNG.normal(size=(3, 6)), jnp.float32)
    got = cc.client_conv(x, w, method=method, bias=b, fused_epilogue=True,
                         interpret=True)
    _close(got, _bias_relu_ref(x, w, b))
    got1 = cc.client_conv(x[0], w[0], method=method, bias=b[0],
                          fused_epilogue=True, interpret=True)
    _close(got1, _bias_relu_ref(x[0], w[0], b[0]))


@pytest.mark.parametrize("method", ["einsum", "pallas"])
def test_fused_epilogue_grads_match_reference(method):
    """Custom VJP unchanged: backward through the einsum-form batched
    GEMMs, ReLU mask recovered from the saved output; dbias = the
    rectified cotangent's row sum."""
    x, w = _xw(3, 2, 8, 8, 3, 6)
    b = jnp.asarray(RNG.normal(size=(3, 6)) * 0.1, jnp.float32)

    def loss(m):
        return lambda w, x, b: jnp.mean(cc.client_conv(
            x, w, method=m, bias=b, fused_epilogue=True,
            interpret=True) ** 2)

    want = jax.grad(loss("conv"), argnums=(0, 1, 2))(w, x, b)
    got = jax.grad(loss(method), argnums=(0, 1, 2))(w, x, b)
    for g, wt in zip(got, want):
        _close(g, wt)


def test_fused_epilogue_pallas_interpret_matches_einsum():
    """Interpret-mode parity: the fused Pallas epilogue kernel == the
    einsum primal + XLA-side bias+ReLU (ragged tile shapes exercised
    through the 128-padding path)."""
    x, w = _xw(2, 3, 7, 11, 4, 8)
    b = jnp.asarray(RNG.normal(size=(2, 8)), jnp.float32)
    got = cc.client_conv(x, w, method="pallas", bias=b,
                         fused_epilogue=True, interpret=True)
    want = cc.client_conv(x, w, method="einsum", bias=b,
                          fused_epilogue=True)
    _close(got, want, tol=1e-6)


def test_conv_block_fused_epilogue_bitwise_on_einsum():
    """On the einsum path (every non-TPU backend) the flag must be a
    bitwise no-op: same float ops in the same order, epilogue fused or
    not — so CPU training runs are unchanged when the flag is threaded
    through AdaSplitHParams."""
    x, w = _xw(3, 2, 8, 8, 3, 6)
    p = {"w": w, "b": jnp.asarray(RNG.normal(size=(3, 6)), jnp.float32)}
    off = lenet._conv_block(p, x, batched_conv=True, conv_method="einsum")
    on = lenet._conv_block(p, x, batched_conv=True, conv_method="einsum",
                           fused_epilogue=True)
    np.testing.assert_array_equal(np.asarray(on), np.asarray(off))


def test_fused_epilogue_trainer_noop_on_cpu(tiny_clients):
    """AdaSplitHParams.fused_epilogue on CPU routes through the einsum
    epilogue — training must be bit-identical to the flag off."""
    from repro.core.adasplit import AdaSplitHParams, AdaSplitTrainer
    assert jax.default_backend() != "tpu"

    def run(**kw):
        hp = AdaSplitHParams(rounds=1, kappa=0.0, batch_size=16, seed=3,
                             **kw)
        tr = AdaSplitTrainer(CFG, hp, tiny_clients)
        tr.train(eval_every=10)
        return tr

    on = run(fused_epilogue=True)
    off = run()
    for a, b in zip(jax.tree.leaves(on.server_params),
                    jax.tree.leaves(off.server_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(on.client_params),
                    jax.tree.leaves(off.client_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
