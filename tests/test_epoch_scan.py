"""Epoch-resident training: differential + metering tests.

``epoch_scan=True`` groups consecutive same-phase rounds into one
dispatch group: a rolled outer ``lax.scan`` whose body applies
``ucb_new_round`` IN-GRAPH at the round boundary and then runs the
round's inner iteration scan — with chunked double-buffered staging
(``epoch_chunk_rounds``) and exactly ONE ``device_get`` per epoch.  It
must reproduce the per-round-dispatch driver: selections and
meter byte totals exactly, per-iteration CE losses (the orchestrator's
L history) and final params to the float tolerances below.

Hypothesis property tests for the round-boundary semantics and the
vectorized billing live in ``test_epoch_properties.py``.
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


@pytest.fixture(scope="module")
def round_ref(clients6):
    """The PR-2 reference: per-round dispatch driver."""
    return _train(clients6)


# The epoch and per-round programs compile separately, and XLA may fuse
# the same round body differently in each, so a sum can come out in
# another order and differ in its last bit (it does under jax 0.9).
# Adam's step is ~lr whatever the gradient's size, so a last-bit change
# in a near-zero gradient grows into a parameter difference of up to
# ~lr per step and into the CE history behind it.  The bounds below are
# the eager-driver test's 2e-4 on params and 1e-3 relative on CE;
# selections, step counts and meter totals stay exact.  The joint
# (server_grad_to_client) step also trains the client towers on the
# server's CE, so the drift re-enters every forward: its params get
# Adam's own bound, lr (1e-3) per step over its 6 global steps
# (measured 3.2e-3 on the server params under jax 0.9).
CE_RTOL = 1e-3
PARAM_ATOL = 2e-4
JOINT_PARAM_ATOL = 6e-3


def _assert_epoch_matches_round(ep, ref, param_atol=PARAM_ATOL):
    # selections exact; per-iteration CE histories to CE_RTOL
    np.testing.assert_array_equal(ep.orch.S, ref.orch.S)
    np.testing.assert_allclose(ep.orch.L, ref.orch.L, rtol=CE_RTOL)
    for a, b in zip(jax.tree.leaves(ep.orch.state),
                    jax.tree.leaves(ref.orch.state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=CE_RTOL)
    assert ep.orch._n_selects == ref.orch._n_selects
    # meter totals: exact (nnz fracs enter the byte totals)
    assert ep.meter.bandwidth_bytes == ref.meter.bandwidth_bytes
    assert ep.meter.client_flops == ref.meter.client_flops
    assert ep.meter.server_flops == ref.meter.server_flops
    # final params to param_atol
    assert _max_leaf_diff(ep.server_params, ref.server_params) <= param_atol
    assert _max_leaf_diff(ep.client_params, ref.client_params) <= param_atol
    assert _max_leaf_diff(ep.masks, ref.masks) <= param_atol
    # per-round history records agree (cumulative meter summaries)
    assert len(ep.history) == len(ref.history)
    for h_e, h_r in zip(ep.history, ref.history):
        assert h_e["round"] == h_r["round"]
        assert h_e["phase"] == h_r["phase"]
        assert h_e["bandwidth_gb"] == h_r["bandwidth_gb"]
        assert h_e["client_tflops"] == h_r["client_tflops"]
        assert ("accuracy" in h_e) == ("accuracy" in h_r)


# ---------------------------------------------------------------------------
# differential: epoch scan == per-round dispatch driver
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [0, 1, 2])
def test_epoch_scan_matches_round_scan(clients6, round_ref, chunk):
    """Multi-round run spanning the local->global phase switch, for
    epoch_chunk_rounds in {R, 1, 2} (0 = whole epoch per dispatch)."""
    ep = _train(clients6, epoch_scan=True, epoch_chunk_rounds=chunk)
    _assert_epoch_matches_round(ep, round_ref)


@pytest.mark.slow
@pytest.mark.parametrize("kw", [
    dict(server_grad_to_client=True),
    dict(mask_mode="per_scalar"),
    dict(act_l1=1e-1, act_threshold=0.5),
], ids=["joint", "per_scalar", "act_l1"])
def test_epoch_scan_matches_round_scan_variants(clients6, kw):
    """>= 3 global rounds in ONE epoch, across the joint / per-scalar /
    activation-sparsified configs."""
    ep = _train(clients6, kappa=0.0, epoch_scan=True, **kw)
    ref = _train(clients6, kappa=0.0, **kw)
    _assert_epoch_matches_round(
        ep, ref, JOINT_PARAM_ATOL if kw.get("server_grad_to_client")
        else PARAM_ATOL)


@pytest.mark.slow
def test_epoch_scan_matches_eager_driver(clients6):
    """Transitivity check against the bottom of the reference ladder:
    the per-iteration eager driver (selections + meters exact, params
    to fp tolerance — eager steps compile separately)."""
    ep = _train(clients6, epoch_scan=True)
    eager = _train(clients6, round_scan=False)
    np.testing.assert_array_equal(ep.orch.S, eager.orch.S)
    assert ep.meter.bandwidth_bytes == eager.meter.bandwidth_bytes
    assert _max_leaf_diff(ep.server_params, eager.server_params) < 2e-4
    assert _max_leaf_diff(ep.client_params, eager.client_params) < 2e-4


# ---------------------------------------------------------------------------
# host-sync discipline: ONE device_get per epoch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [0, 1])
def test_epoch_scan_single_sync_per_epoch(clients6, monkeypatch, chunk):
    """2 local + 2 global rounds = 2 epochs; the local epoch performs
    no fetch at all, the global epoch exactly one — regardless of how
    many staging chunks the epoch is split into."""
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    _train(clients6, rounds=4, kappa=0.5, epoch_scan=True,
           epoch_chunk_rounds=chunk)
    assert calls["n"] == 1


def test_epoch_scan_empty_rounds_still_reset_bandit(clients6):
    """T==0 (datasets smaller than the batch) runs nothing, but the
    per-round driver still resets the bandit every round — the epoch
    driver must too, or the ladder's states diverge."""
    ep = _train(clients6, batch_size=64, epoch_scan=True)   # 32 < 64
    ref = _train(clients6, batch_size=64)
    for a, b in zip(jax.tree.leaves(ep.orch.state),
                    jax.tree.leaves(ref.orch.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(ep.orch.L, ref.orch.L)
    assert ep.meter.bandwidth_bytes == ref.meter.bandwidth_bytes == 0.0
    assert len(ep.history) == len(ref.history)


def test_epoch_scan_eval_cadence_bounds_epochs(clients6):
    """eval_every cuts the dispatch groups: with eval_every=1 every
    round is its own epoch and every round records an accuracy —
    identical history structure to the per-round driver."""
    hp = AdaSplitHParams(rounds=3, kappa=0.34, batch_size=16, seed=7,
                         epoch_scan=True)
    tr = AdaSplitTrainer(CFG, hp, clients6)
    tr.train(eval_every=1)
    assert [h["round"] for h in tr.history] == [0, 1, 2]
    assert all("accuracy" in h for h in tr.history)


# ---------------------------------------------------------------------------
# round boundaries: in-graph ucb_new_round == host new_round calls
# (deterministic case; the hypothesis sweep lives in
# test_epoch_properties.py)
# ---------------------------------------------------------------------------


def test_epoch_ucb_round_boundaries_bitwise_deterministic():
    from repro.core.orchestrator import (Orchestrator, ucb_new_round,
                                         ucb_select, ucb_update)
    n, k, R, T, gamma = 6, 3, 3, 2, 0.87
    rng = np.random.default_rng(11)
    losses = rng.uniform(0.1, 8.0, (R, T, n)).astype(np.float32)

    host = Orchestrator(n, eta=k / n, gamma=gamma, seed=4)
    sel_host = []
    for r in range(R):
        host.new_round()
        for t in range(T):
            sel = host.select()
            sel_host.append(sel)
            host.update(sel, losses[r, t][sel])

    dev = Orchestrator(n, eta=k / n, gamma=gamma, seed=4)
    base_key = dev._base_key

    def round_body(carry, xs):
        ucb, t0 = carry
        ucb = ucb_new_round(ucb, gamma=gamma)
        ucb = jax.lax.optimization_barrier(ucb)

        def it(carry, dense_losses):
            ucb, t = carry
            idx = ucb_select(ucb, k, jax.random.fold_in(base_key, t))
            sel = jnp.zeros((n,), jnp.float32).at[idx].set(1.0)
            dense = jnp.zeros((n,), jnp.float32).at[idx].set(
                dense_losses[idx])
            ucb = ucb_update(ucb, sel, dense, gamma=gamma)
            return (ucb, t + 1), (idx, dense_losses[idx])

        return jax.lax.scan(it, (ucb, t0), xs)

    @jax.jit
    def epoch(ucb, losses):
        return jax.lax.scan(round_body, (ucb, jnp.asarray(0, jnp.int32)),
                            losses)

    (ucb, _), (idx_all, ces_all) = epoch(dev.state, jnp.asarray(losses))
    dev.ingest_epoch(np.asarray(idx_all), np.asarray(ces_all), state=ucb)

    np.testing.assert_array_equal(
        np.asarray(idx_all).reshape(R * T, k), np.stack(sel_host))
    for a, b in zip(jax.tree.leaves(dev.state),
                    jax.tree.leaves(host.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(dev.L, host.L)
    np.testing.assert_array_equal(dev.S, host.S)
    assert dev._n_selects == host._n_selects


# ---------------------------------------------------------------------------
# Meter.ingest_epoch == sequential ingest_round (deterministic case;
# the hypothesis sweep lives in test_epoch_properties.py)
# ---------------------------------------------------------------------------


def test_meter_ingest_epoch_matches_sequential_rounds():
    R, T, k, n, batch = 3, 2, 4, 8, 16
    shape = (batch, 8, 8, 16)
    fl_c, fl_s = 1.5e6, 2.5e6
    fracs = np.linspace(0.05, 0.95, R * T * k).reshape(R, T, k) \
        .astype(np.float32)
    kw = dict(acts_shape=shape, batch=batch, n_clients=n, n_iters=T,
              client_flops_per_example=fl_c,
              server_flops_per_example=fl_s, n_selected=k)
    m1 = Meter()
    summaries = m1.ingest_epoch(n_rounds=R, nnz_fracs=fracs, **kw)
    m2 = Meter()
    want = []
    for r in range(R):
        m2.ingest_round(nnz_fracs=fracs[r], **kw)
        want.append(m2.summary())
    assert m1.bandwidth_bytes == m2.bandwidth_bytes
    assert m1.client_flops == m2.client_flops
    assert m1.server_flops == m2.server_flops
    assert summaries == want
    # and the per-event contract still holds through the batch helper
    m3 = Meter()
    for r in range(R):
        for t in range(T):
            m3.add_client_flops(3 * fl_c * n * batch)
            for j in range(k):
                m3.add_payload(split_payload_bytes(
                    shape, batch, nnz_fraction=float(fracs[r, t, j])))
                m3.add_server_flops(3 * fl_s * batch)
    assert m1.bandwidth_bytes == m3.bandwidth_bytes
    assert m1.client_flops == m3.client_flops
    assert m1.server_flops == m3.server_flops


# ---------------------------------------------------------------------------
# LM path: windowed dispatch == per-step dispatch
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_lm_windowed_matches_per_step():
    """``epoch_scan=True`` on the LM trainer scans whole log windows in
    one dispatch (launch.steps.build_windowed_ucb_step): CE / l_client
    histories, meter totals, UCB state and trainables must match the
    per-step driver bitwise (same fold-in key schedule)."""
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import LaunchPolicy
    from repro.launch.train import LMAdaSplitTrainer
    cfg = get_config("qwen2-0.5b").reduced()
    mesh = make_host_mesh()
    shape = InputShape("t", 64, 8, "train")
    pol = LaunchPolicy(fsdp=False, microbatch=1, seq_shard=False)

    a = LMAdaSplitTrainer(cfg, mesh, shape, pol, kappa=0.5)
    ha = a.run(6, log_every=3)
    b = LMAdaSplitTrainer(cfg, mesh, shape, pol, kappa=0.5,
                          epoch_scan=True)
    hb = b.run(6, log_every=3)

    assert [h["ce"] for h in ha] == [h["ce"] for h in hb]
    assert [h["l_client"] for h in ha] == [h["l_client"] for h in hb]
    assert [h["phase"] for h in ha] == [h["phase"] for h in hb]
    assert a.meter.bandwidth_bytes == b.meter.bandwidth_bytes
    assert a.meter.client_flops == b.meter.client_flops
    for x, y in zip(jax.tree.leaves(a.ucb), jax.tree.leaves(b.ucb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(a.state["trainables"]),
                    jax.tree.leaves(b.state["trainables"])):
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(x, jnp.float32)),
            np.asarray(jnp.asarray(y, jnp.float32)))


@pytest.mark.slow
def test_lm_windowed_one_dispatch_sync_per_window(monkeypatch):
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import LaunchPolicy
    from repro.launch.train import LMAdaSplitTrainer
    cfg = get_config("qwen2-0.5b").reduced()
    mesh = make_host_mesh()
    shape = InputShape("t", 64, 8, "train")
    pol = LaunchPolicy(fsdp=False, microbatch=1, seq_shard=False)
    tr = LMAdaSplitTrainer(cfg, mesh, shape, pol, kappa=0.5,
                           epoch_scan=True)

    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    hist = tr.run(6, log_every=3)
    assert calls["n"] == 2                   # one per window
    assert len(hist) == 6
    assert hist[0]["phase"] == "local" and hist[-1]["phase"] == "global"
    assert np.isfinite(hist[-1]["ce"]) and hist[-1]["ce"] > 0
    assert hist[-1]["bandwidth_gb"] > 0
