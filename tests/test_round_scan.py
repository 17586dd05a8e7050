"""Device-resident rounds: differential + metering tests.

The round scan (``round_scan=True``, the default) runs all T
iterations of a round — client step, in-graph UCB selection, batched
global step, bandit update — under ONE jitted ``lax.scan`` with a
single ``device_get`` per round.  It must reproduce the eager
per-iteration driver: selections EXACTLY (same keyed-jitter schedule),
meter totals bit-for-bit, params/accuracy to fp tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.accounting import Meter, split_payload_bytes
from repro.core.adasplit import AdaSplitHParams, AdaSplitTrainer
from repro.data.synthetic import mixed_noniid

CFG = get_config("lenet-cifar")


@pytest.fixture(scope="module")
def clients6():
    return mixed_noniid(n_clients=6, n_per_client=32, n_test=16, seed=0)


def _train(clients, **kw):
    defaults = dict(rounds=3, kappa=0.34, batch_size=16, seed=7)
    defaults.update(kw)
    tr = AdaSplitTrainer(CFG, AdaSplitHParams(**defaults), clients)
    tr.train(eval_every=10)
    return tr


def _max_leaf_diff(a, b):
    return max(float(jnp.abs(x - y).max()) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _assert_scan_matches_eager(scan, eager, tol=2e-4):
    # selections exactly: the full per-round selection history agrees
    np.testing.assert_array_equal(scan.orch.S, eager.orch.S)
    np.testing.assert_allclose(scan.orch.L, eager.orch.L,
                               rtol=1e-4, atol=1e-4)
    # meter totals bit-for-bit (same accumulation event order)
    assert scan.meter.bandwidth_bytes == eager.meter.bandwidth_bytes
    assert scan.meter.server_flops == eager.meter.server_flops
    assert scan.meter.client_flops == eager.meter.client_flops
    # model state to fp tolerance (different XLA fusion boundaries)
    assert _max_leaf_diff(scan.server_params, eager.server_params) < tol
    assert _max_leaf_diff(scan.client_params, eager.client_params) < tol
    assert _max_leaf_diff(scan.masks, eager.masks) < tol
    acc_s = scan.history[-1]["accuracy"]
    acc_e = eager.history[-1]["accuracy"]
    assert abs(acc_s - acc_e) < 1.0, (acc_s, acc_e)


# ---------------------------------------------------------------------------
# differential: round scan == eager per-iteration driver
# ---------------------------------------------------------------------------


def test_round_scan_matches_eager_full_run(clients6):
    """Multi-round run spanning the local->global phase switch."""
    scan = _train(clients6)
    eager = _train(clients6, round_scan=False)
    _assert_scan_matches_eager(scan, eager)


@pytest.mark.slow
@pytest.mark.parametrize("kw", [
    dict(server_grad_to_client=True),
    dict(serialize_server_updates=True),
    dict(mask_mode="per_scalar"),
    dict(act_l1=1e-1, act_threshold=0.5),
], ids=["joint", "serialized", "per_scalar", "act_l1"])
def test_round_scan_matches_eager_variants(clients6, kw):
    scan = _train(clients6, kappa=0.0, rounds=2, **kw)
    eager = _train(clients6, kappa=0.0, rounds=2, round_scan=False, **kw)
    _assert_scan_matches_eager(scan, eager)


@pytest.mark.slow
def test_flat_joint_matches_vmap_joint(clients6):
    """Satellite: the S*B segment-reduction joint step == the vmapped
    per-client reference (same updates to fp tolerance)."""
    flat = _train(clients6, kappa=0.0, rounds=2, round_scan=False,
                  server_grad_to_client=True)
    ref = _train(clients6, kappa=0.0, rounds=2, round_scan=False,
                 server_grad_to_client=True, flat_joint=False)
    # the two joint lowerings (S*B segment reduction vs vmap) compile
    # different reduction orders; 2 rounds of Adam amplify the fp32
    # drift to a few e-4 on CPU BLAS — fp-class, selections stay exact
    assert _max_leaf_diff(flat.client_params, ref.client_params) < 1e-4
    assert _max_leaf_diff(flat.server_params, ref.server_params) < 1e-3
    assert _max_leaf_diff(flat.masks, ref.masks) < 1e-3
    np.testing.assert_array_equal(flat.orch.S, ref.orch.S)
    assert flat.meter.bandwidth_bytes == ref.meter.bandwidth_bytes


# ---------------------------------------------------------------------------
# batched-GEMM convs (tentpole): reference-path differential
# ---------------------------------------------------------------------------


def test_batched_conv_matches_reference_path(clients6):
    """``batched_conv=True`` (the im2col batched-GEMM lowering) vs the
    ``lax.conv_general_dilated`` reference: selections and meter totals
    bit-identical, model state to fp tolerance."""
    gemm = _train(clients6)                       # batched_conv default on
    ref = _train(clients6, batched_conv=False)
    np.testing.assert_array_equal(gemm.orch.S, ref.orch.S)
    assert gemm.meter.bandwidth_bytes == ref.meter.bandwidth_bytes
    assert gemm.meter.client_flops == ref.meter.client_flops
    assert gemm.meter.server_flops == ref.meter.server_flops
    assert _max_leaf_diff(gemm.server_params, ref.server_params) < 2e-4
    assert _max_leaf_diff(gemm.client_params, ref.client_params) < 2e-4
    assert _max_leaf_diff(gemm.masks, ref.masks) < 2e-4
    acc_g = gemm.history[-1]["accuracy"]
    acc_r = ref.history[-1]["accuracy"]
    assert abs(acc_g - acc_r) < 1.0, (acc_g, acc_r)


@pytest.mark.slow
def test_batched_conv_matches_reference_per_scalar(clients6):
    """Per-scalar masks vmap the server conv with per-client effective
    weights — the other grouped-conv site the GEMM form replaces."""
    gemm = _train(clients6, kappa=0.0, rounds=2, mask_mode="per_scalar")
    ref = _train(clients6, kappa=0.0, rounds=2, mask_mode="per_scalar",
                 batched_conv=False)
    np.testing.assert_array_equal(gemm.orch.S, ref.orch.S)
    assert gemm.meter.bandwidth_bytes == ref.meter.bandwidth_bytes
    assert _max_leaf_diff(gemm.server_params, ref.server_params) < 2e-4
    assert _max_leaf_diff(gemm.masks, ref.masks) < 2e-4


# ---------------------------------------------------------------------------
# host-sync discipline: ONE device_get per global round
# ---------------------------------------------------------------------------


def test_round_scan_single_sync_per_round(clients6, monkeypatch):
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    _train(clients6, rounds=2, kappa=0.5)    # 1 local + 1 global round
    assert calls["n"] == 1                   # local rounds sync nothing


# ---------------------------------------------------------------------------
# Meter.ingest_round == the eager per-event accumulation
# ---------------------------------------------------------------------------


def test_meter_ingest_round_matches_manual_accumulation():
    acts_shape, batch, n, T, k = (16, 8, 8, 16), 16, 8, 3, 4
    fl_c, fl_s = 1.5e6, 2.5e6
    fracs = np.linspace(0.1, 0.9, T * k).reshape(T, k)

    m1 = Meter()
    m1.ingest_round(acts_shape=acts_shape, batch=batch, n_clients=n,
                    n_iters=T, client_flops_per_example=fl_c,
                    server_flops_per_example=fl_s, nnz_fracs=fracs)
    m2 = Meter()
    for t in range(T):
        m2.add_client_flops(3 * fl_c * n * batch)
        for j in range(k):
            m2.add_payload(split_payload_bytes(
                acts_shape, batch, nnz_fraction=float(fracs[t, j])))
            m2.add_server_flops(3 * fl_s * batch)
    assert m1.bandwidth_bytes == m2.bandwidth_bytes
    assert m1.client_flops == m2.client_flops
    assert m1.server_flops == m2.server_flops

    # dense billing + grad_down + bf16 payloads
    m3 = Meter()
    m3.ingest_round(acts_shape=acts_shape, batch=batch, n_clients=n,
                    n_iters=2, client_flops_per_example=fl_c,
                    server_flops_per_example=fl_s, n_selected=k,
                    grad_down=True, dtype_bytes=2)
    per = split_payload_bytes(acts_shape, batch, grad_down=True,
                              dtype_bytes=2)
    assert m3.bandwidth_bytes == 2 * k * per


def test_split_payload_bytes_dtype_bytes():
    shape, b = (4, 8, 16), 4                  # 512 elements
    assert split_payload_bytes(shape, b) == 512 * 4 + 4 * 4
    assert split_payload_bytes(shape, b, dtype_bytes=2) == 512 * 2 + 4 * 4
    assert split_payload_bytes(shape, b, dtype_bytes=2, grad_down=True) \
        == 512 * 2 + 4 * 4 + 512 * 2
    # sparse bf16: nnz * (2B value + 4B int32 index)
    assert split_payload_bytes(shape, b, dtype_bytes=2,
                               nnz_fraction=0.25) == 128 * 6 + 4 * 4


# ---------------------------------------------------------------------------
# fused masked-Adam wiring (satellite): CPU fallback + interpret parity
# ---------------------------------------------------------------------------


def test_fused_adam_update_matches_adam_update():
    from repro.kernels.masked_adam import fused_adam_update
    from repro.optim.adam import adam_init, adam_update
    rng = np.random.default_rng(0)
    params = {"a": jnp.asarray(rng.normal(size=(33, 47)), jnp.float32),
              "b": [jnp.asarray(rng.normal(size=(129,)), jnp.float32)]}
    grads = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32),
        params)
    opt = adam_init(params)
    p_ref, o_ref = adam_update(params, grads, opt, lr=1e-3)
    p_fused, o_fused = fused_adam_update(params, grads, opt, lr=1e-3,
                                         interpret=True)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_fused)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    for a, b in zip(jax.tree.leaves(o_ref["mu"]),
                    jax.tree.leaves(o_fused["mu"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    assert int(o_fused["step"]) == 1

    # explicit gradient mask freezes masked entries
    mask = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    p_frozen, _ = fused_adam_update(params, grads, opt, lr=1e-3,
                                    mask=mask, interpret=True)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p_frozen)):
        np.testing.assert_allclose(a, b)


def test_fused_mask_adam_flag_is_noop_off_tpu(clients6):
    """Off TPU the backend default (None) resolves to plain adam_update
    — the same run as an explicit False — and an explicit True is
    refused: the kernel cannot lower natively here, and nothing falls
    back to plain Adam behind the caller's back."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(ValueError, match="fused Pallas Adam"):
        AdaSplitTrainer(CFG, AdaSplitHParams(fused_mask_adam=True),
                        clients6)
    on = _train(clients6, rounds=1, kappa=0.0, fused_mask_adam=None)
    off = _train(clients6, rounds=1, kappa=0.0, fused_mask_adam=False)
    assert _max_leaf_diff(on.masks, off.masks) == 0.0
    assert _max_leaf_diff(on.server_params, off.server_params) == 0.0


def test_fused_server_adam_interpret_parity():
    """Satellite: the server Adam step through the fused Pallas kernel
    (interpret mode) == plain adam_update on server-shaped params."""
    from repro.configs.base import get_config
    from repro.kernels.masked_adam import fused_adam_update
    from repro.models import lenet
    from repro.optim.adam import adam_init, adam_update
    sp = lenet.init_server_params(get_config("lenet-cifar"),
                                  jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    grads = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32), sp)
    opt = adam_init(sp)
    p_ref, o_ref = adam_update(sp, grads, opt, lr=1e-3)
    p_fused, o_fused = fused_adam_update(sp, grads, opt, lr=1e-3,
                                         interpret=True)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_fused)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    for a, b in zip(jax.tree.leaves(o_ref["nu"]),
                    jax.tree.leaves(o_fused["nu"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    assert int(o_fused["step"]) == 1


def test_fused_server_adam_flag_is_noop_off_tpu(clients6):
    """``fused_server_adam`` resolves exactly like the mask flag: off
    TPU the default is plain adam_update and an explicit True raises."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(ValueError, match="fused Pallas Adam"):
        AdaSplitTrainer(CFG, AdaSplitHParams(fused_server_adam=True),
                        clients6)
    on = _train(clients6, rounds=1, kappa=0.0, fused_server_adam=None)
    off = _train(clients6, rounds=1, kappa=0.0, fused_server_adam=False)
    assert _max_leaf_diff(on.server_params, off.server_params) == 0.0
    assert _max_leaf_diff(on.masks, off.masks) == 0.0


# ---------------------------------------------------------------------------
# LM path: no per-step host sync in the global phase
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_lm_trainer_defers_host_sync(monkeypatch):
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import LaunchPolicy
    from repro.launch.train import LMAdaSplitTrainer
    cfg = get_config("qwen2-0.5b").reduced()
    mesh = make_host_mesh()
    shape = InputShape("t", 64, 8, "train")
    pol = LaunchPolicy(fsdp=False, microbatch=1, seq_shard=False)
    tr = LMAdaSplitTrainer(cfg, mesh, shape, pol, kappa=0.5)

    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    hist = tr.run(6, log_every=3)
    assert calls["n"] == 2                   # one drain per log window
    assert len(hist) == 6
    assert hist[0]["phase"] == "local" and hist[-1]["phase"] == "global"
    assert np.isfinite(hist[-1]["ce"]) and hist[-1]["ce"] > 0
    assert hist[-1]["bandwidth_gb"] > 0
    # billing went through split_payload_bytes with bf16 activations
    b = shape.global_batch // tr.C
    per = split_payload_bytes((b, shape.seq_len, cfg.d_model), b,
                              dtype_bytes=2)
    assert tr.meter.bandwidth_bytes == 3 * tr.k * per
