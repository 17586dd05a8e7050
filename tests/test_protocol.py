"""AdaSplit protocol invariants (paper §3) on the paper-scale trainer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_config
from repro.core.adasplit import AdaSplitHParams, AdaSplitTrainer
from repro.core.c3 import c3_score
from repro.core.orchestrator import Orchestrator


CFG = get_config("lenet-cifar")


def _trainer(tiny_clients, **kw):
    defaults = dict(rounds=3, kappa=0.34, batch_size=16)
    defaults.update(kw)
    return AdaSplitTrainer(CFG, AdaSplitHParams(**defaults), tiny_clients)


@pytest.mark.slow
def test_local_phase_has_zero_bandwidth(tiny_clients):
    """P_is = 0 for all rounds r < kappa*R (paper §3.2)."""
    tr = _trainer(tiny_clients, rounds=3, kappa=1.0)  # all local
    tr.train(eval_every=10)
    assert tr.meter.bandwidth_bytes == 0.0
    assert tr.meter.server_flops == 0.0  # server never trains either


@pytest.mark.slow
def test_global_phase_meters_bandwidth(tiny_clients):
    tr = _trainer(tiny_clients, rounds=2, kappa=0.0)
    tr.train(eval_every=10)
    assert tr.meter.bandwidth_bytes > 0
    assert tr.meter.server_flops > 0


@pytest.mark.slow
def test_no_server_gradient_to_client(tiny_clients):
    """P_si = 0: client params after a global step must be identical
    whether or not the server trained on the activations (the client
    update uses only L_client)."""
    hp = AdaSplitHParams(rounds=1, kappa=0.0, batch_size=16, seed=7)
    tr1 = AdaSplitTrainer(CFG, hp, tiny_clients)
    tr1.train(eval_every=10)
    hp2 = AdaSplitHParams(rounds=1, kappa=1.0, batch_size=16, seed=7)
    tr2 = AdaSplitTrainer(CFG, hp2, tiny_clients)
    tr2.train(eval_every=10)
    # same seed, same data order -> client params identical across
    # kappa=0 (server trained) and kappa=1 (server idle)
    for a, b in zip(jax.tree.leaves(tr1.client_params),
                    jax.tree.leaves(tr2.client_params)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_server_grad_ablation_changes_client(tiny_clients):
    """Table-5 ablation flag routes server CE grad into the client."""
    hp = AdaSplitHParams(rounds=1, kappa=0.0, batch_size=16, seed=7,
                         server_grad_to_client=True)
    tr = AdaSplitTrainer(CFG, hp, tiny_clients)
    tr.train(eval_every=10)
    hp2 = AdaSplitHParams(rounds=1, kappa=0.0, batch_size=16, seed=7)
    tr2 = AdaSplitTrainer(CFG, hp2, tiny_clients)
    tr2.train(eval_every=10)
    diffs = [float(jnp.abs(a - b).max()) for a, b in
             zip(jax.tree.leaves(tr.client_params),
                 jax.tree.leaves(tr2.client_params))]
    assert max(diffs) > 1e-6
    # and bandwidth doubles (activation grads travel server->client)
    assert tr.meter.bandwidth_bytes > 1.5 * tr2.meter.bandwidth_bytes


@pytest.mark.slow
def test_high_lambda_shrinks_masks(tiny_clients):
    """L1 drives mask magnitudes down (Adam makes the step size
    scale-free in lambda, so at few-round horizons we check the mean
    magnitude, not a hard sparsity threshold)."""
    import jax.numpy as jnp

    def mean_abs(masks):
        leaves = jax.tree.leaves(masks)
        return float(sum(jnp.sum(jnp.abs(m)) for m in leaves)
                     / sum(m.size for m in leaves))

    tr_hi = _trainer(tiny_clients, rounds=3, kappa=0.0, lam=10.0, seed=1)
    tr_hi.train(eval_every=10)
    tr_lo = _trainer(tiny_clients, rounds=3, kappa=0.0, lam=0.0, seed=1)
    tr_lo.train(eval_every=10)
    assert mean_abs(tr_hi.masks) < mean_abs(tr_lo.masks)
    assert mean_abs(tr_hi.masks) < 1.0  # moved off the init


@pytest.mark.slow
def test_activation_sparsification_reduces_payload(tiny_clients):
    """Table 6: the beta (act_l1) knob cuts bandwidth.  Sparse payloads
    cost nnz*(value+index) bytes, so the win needs nnz < 50% — use an
    aggressive threshold as the paper's extreme-budget point."""
    tr_d = _trainer(tiny_clients, rounds=2, kappa=0.0, seed=3)
    tr_d.train(eval_every=10)
    tr_s = _trainer(tiny_clients, rounds=2, kappa=0.0, seed=3,
                    act_l1=1e-1, act_threshold=1.0)
    tr_s.train(eval_every=10)
    assert tr_s.meter.bandwidth_bytes < tr_d.meter.bandwidth_bytes


# ---------------------------------------------------------------------------
# Orchestrator (eq. 6)
# ---------------------------------------------------------------------------


def test_orchestrator_selects_eta_fraction():
    o = Orchestrator(10, eta=0.6, gamma=0.87)
    sel = o.select()
    assert len(sel) == 6
    assert len(set(sel.tolist())) == 6


def test_orchestrator_prioritizes_high_loss_clients():
    o = Orchestrator(4, eta=0.5, gamma=0.9)
    # feed many iterations: clients 0,1 keep high loss, 2,3 low
    for _ in range(30):
        sel = o.select()
        losses = [10.0 if i < 2 else 0.1 for i in sel]
        o.update(sel, losses)
    counts = np.zeros(4)
    for _ in range(20):
        sel = o.select()
        losses = [10.0 if i < 2 else 0.1 for i in sel]
        o.update(sel, losses)
        counts[sel] += 1
    assert counts[:2].sum() > counts[2:].sum()  # exploitation


def test_orchestrator_unselected_loss_decay():
    o = Orchestrator(3, eta=0.34)
    sel = o.select()
    o.update(sel, [5.0] * len(sel))
    unsel = [i for i in range(3) if i not in set(sel.tolist())]
    for i in unsel:
        assert o.L[i][-1] == (o.L[i][-2] + o.L[i][-3]) / 2.0


# ---------------------------------------------------------------------------
# Functional UCB orchestrator (hypothesis twins of
# test_orchestrator_device.py's numpy-randomized invariants)
# ---------------------------------------------------------------------------


@given(n=st.integers(2, 16), data=st.data(), key_seed=st.integers(0, 99))
@settings(max_examples=30, deadline=None)
def test_ucb_select_property(n, data, key_seed):
    """k distinct in-range sorted ids, for ANY reachable state."""
    from repro.core.orchestrator import ucb_init, ucb_select, ucb_update
    k = data.draw(st.integers(1, n))
    state = ucb_init(n, gamma=0.87)
    for _ in range(data.draw(st.integers(0, 3))):
        mask = np.zeros(n, np.float32)
        sel = data.draw(st.lists(st.integers(0, n - 1), min_size=1,
                                 max_size=n, unique=True))
        mask[sel] = 1.0
        losses = np.asarray(data.draw(st.lists(
            st.floats(0.0, 50.0), min_size=n, max_size=n)), np.float32)
        state = ucb_update(state, jnp.asarray(mask), jnp.asarray(losses),
                           gamma=0.87)
    idx = np.asarray(ucb_select(state, k, jax.random.PRNGKey(key_seed)))
    assert idx.shape == (k,)
    assert len(set(idx.tolist())) == k
    assert ((0 <= idx) & (idx < n)).all()
    assert (np.diff(idx) >= 1).all() or k == 1


@given(n=st.integers(2, 12), data=st.data(),
       gamma=st.floats(0.5, 0.99))
@settings(max_examples=30, deadline=None)
def test_ucb_update_and_reset_property(n, data, gamma):
    """Selected clients take their CE, unselected decay by the
    two-point mean; new_round resets to L=[last, last], S=[1, 1]."""
    from repro.core.orchestrator import ucb_init, ucb_new_round, ucb_update
    state = ucb_init(n, gamma=gamma)
    sel = data.draw(st.lists(st.integers(0, n - 1), min_size=1,
                             max_size=n, unique=True))
    mask = np.zeros(n, np.float32)
    mask[sel] = 1.0
    losses = np.asarray(data.draw(st.lists(
        st.floats(0.0, 50.0), min_size=n, max_size=n)), np.float32)
    last = np.asarray(state["last"])
    prev = np.asarray(state["prev"])
    s0 = np.asarray(state["s_disc"])
    new = ucb_update(state, jnp.asarray(mask), jnp.asarray(losses),
                     gamma=gamma)
    exp_l = (last + prev) / 2.0
    exp_l[sel] = losses[sel]
    np.testing.assert_allclose(np.asarray(new["last"]), exp_l,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new["s_disc"]),
                               gamma * s0 + mask, rtol=1e-5)
    assert int(new["t"]) == int(state["t"]) + 1

    reset = ucb_new_round(new, gamma=gamma)
    # XLA flushes subnormal results to zero where numpy keeps them: a
    # drawn loss of 1e-45 differs by less than the smallest normal f32
    np.testing.assert_allclose(np.asarray(reset["l_disc"]),
                               np.asarray(new["last"]) * (1 + gamma),
                               rtol=1e-5, atol=np.finfo(np.float32).tiny)
    np.testing.assert_allclose(np.asarray(reset["s_disc"]),
                               np.full(n, 1 + gamma, np.float32),
                               rtol=1e-5)
    assert int(reset["t"]) == 2


# ---------------------------------------------------------------------------
# C3-Score (eq. 9) properties
# ---------------------------------------------------------------------------


@given(acc=st.floats(1.0, 100.0), bw=st.floats(0.0, 100.0),
       comp=st.floats(0.0, 100.0))
@settings(max_examples=50, deadline=None)
def test_c3_bounded(acc, bw, comp):
    s = c3_score(acc, bw, comp, bandwidth_budget=10.0, compute_budget=10.0)
    assert 0.0 <= s <= 1.0


@given(acc=st.floats(10.0, 100.0), bw=st.floats(0.1, 50.0),
       delta=st.floats(0.1, 50.0))
@settings(max_examples=50, deadline=None)
def test_c3_monotone_decreasing_in_cost(acc, bw, delta):
    lo = c3_score(acc, bw, 1.0, bandwidth_budget=10.0, compute_budget=10.0)
    hi = c3_score(acc, bw + delta, 1.0, bandwidth_budget=10.0,
                  compute_budget=10.0)
    assert hi < lo


@given(a1=st.floats(1.0, 99.0), delta=st.floats(0.1, 1.0))
@settings(max_examples=50, deadline=None)
def test_c3_monotone_increasing_in_accuracy(a1, delta):
    lo = c3_score(a1, 1.0, 1.0, bandwidth_budget=10.0, compute_budget=10.0)
    hi = c3_score(min(a1 + delta, 100.0), 1.0, 1.0,
                  bandwidth_budget=10.0, compute_budget=10.0)
    assert hi > lo


# ---------------------------------------------------------------------------
# Batched global phase: gather/scatter round-trip (property)
# ---------------------------------------------------------------------------


@given(n=st.integers(2, 12), data=st.data())
@settings(max_examples=30, deadline=None)
def test_mask_gather_scatter_roundtrip_property(n, data):
    """gather_clients/scatter_clients round-trip for ARBITRARY selection
    subsets — the invariant the batched global phase rests on."""
    from repro.core import masks as masks_mod
    idx = data.draw(st.lists(st.integers(0, n - 1), min_size=1, max_size=n,
                             unique=True))
    tree = {"a": jnp.arange(n * 3.0).reshape(n, 3),
            "b": [jnp.arange(n * 2.0).reshape(n, 2) + 7.0,
                  jnp.arange(float(n))]}
    jidx = jnp.asarray(np.asarray(idx))
    sel = masks_mod.gather_clients(tree, jidx)
    back = masks_mod.scatter_clients(tree, jidx, sel)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # writes land exactly on the selected rows
    out = masks_mod.scatter_clients(tree, jidx,
                                    jax.tree.map(lambda l: l + 1.0, sel))
    chosen = set(idx)
    for lin, lout in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        for r in range(n):
            exp = lin[r] + 1.0 if r in chosen else lin[r]
            np.testing.assert_array_equal(np.asarray(lout[r]),
                                          np.asarray(exp))


def test_c3_matches_paper_scale():
    """Paper Table 1: SL-basic (84.65, 84.54GB, 3.76T) -> 0.72 with the
    table's budgets.  Our T=8 back-solve should land within 0.04."""
    s = c3_score(84.65, 84.54, 3.76, bandwidth_budget=84.64,
                 compute_budget=17.13, temperature=8.0)
    assert abs(s - 0.72) < 0.04
