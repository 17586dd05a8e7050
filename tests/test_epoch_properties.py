"""Round-boundary + billing property tests for epoch-resident
training (hypothesis).

Satellites of the epoch-scan PR: the in-graph ``ucb_new_round`` at the
scan's round boundary must match R host-driven ``new_round()`` calls
(selections and jitter keys bitwise, discounted sums to f32 rounding),
and the numpy-vectorized batch billing must reproduce the per-event
Python loop's integer byte totals exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core.accounting import (Meter, batch_payload_bytes,
                                   split_payload_bytes)
from repro.core.orchestrator import (Orchestrator, ucb_new_round,
                                     ucb_select, ucb_update)


# ---------------------------------------------------------------------------
# round-boundary semantics: in-graph ucb_new_round == host new_round
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=20)
@given(st.data())
def test_epoch_ucb_round_boundaries_bitwise(data):
    """A jitted scan over R rounds — ``ucb_new_round`` at each boundary,
    ``ucb_select``/``ucb_update`` per iteration, the SAME fold-in key
    schedule — matches R host-driven ``new_round()`` + per-iteration
    ``select()``/``update()`` calls: jittered selections and the
    replayed L/S histories bitwise, discounted sums and last/prev losses
    to f32 rounding."""
    n = data.draw(st.integers(3, 8), label="n")
    k = data.draw(st.integers(1, n), label="k")
    R = data.draw(st.integers(1, 3), label="R")
    T = data.draw(st.integers(1, 3), label="T")
    seed = data.draw(st.integers(0, 5), label="seed")
    gamma = 0.87
    rng = np.random.default_rng(seed)
    losses = rng.uniform(0.1, 8.0, (R, T, n)).astype(np.float32)

    host = Orchestrator(n, eta=k / n, gamma=gamma, seed=seed)
    host.k = k
    sel_host = []
    for r in range(R):
        host.new_round()
        for t in range(T):
            sel = host.select()
            sel_host.append(sel)
            host.update(sel, losses[r, t][sel])

    dev = Orchestrator(n, eta=k / n, gamma=gamma, seed=seed)
    dev.k = k
    base_key = dev._base_key

    def round_body(carry, xs):
        ucb, t0 = carry
        loss_r = xs
        ucb = ucb_new_round(ucb, gamma=gamma)
        # same barrier as the trainer's epoch body: keep the boundary
        # reset out of the first update's FMA fusion
        ucb = jax.lax.optimization_barrier(ucb)

        def it(carry, xs):
            ucb, t = carry
            dense_losses = xs
            key = jax.random.fold_in(base_key, t)
            idx = ucb_select(ucb, k, key)
            sel = jnp.zeros((n,), jnp.float32).at[idx].set(1.0)
            dense = jnp.zeros((n,), jnp.float32).at[idx].set(
                dense_losses[idx])
            ucb = ucb_update(ucb, sel, dense, gamma=gamma)
            return (ucb, t + 1), (idx, dense_losses[idx])

        (ucb, t0), outs = jax.lax.scan(it, (ucb, t0), loss_r)
        return (ucb, t0), outs

    @jax.jit
    def epoch(ucb, losses):
        return jax.lax.scan(round_body, (ucb, jnp.asarray(0, jnp.int32)),
                            losses)

    (ucb, _), (idx_all, ces_all) = epoch(dev.state, jnp.asarray(losses))
    dev.ingest_epoch(np.asarray(idx_all), np.asarray(ces_all), state=ucb)

    # selections bitwise
    np.testing.assert_array_equal(
        np.asarray(idx_all).reshape(R * T, k), np.stack(sel_host))
    # functional state to a few f32 ULPs: inside the scan XLA may
    # contract ``gamma * l_disc + new_l`` into one FMA where the host's
    # eager ops round twice (1 ULP = 6e-8 relative)
    for a, b in zip(jax.tree.leaves(dev.state),
                    jax.tree.leaves(host.state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6)
    # replayed host histories bitwise
    np.testing.assert_array_equal(dev.L, host.L)
    np.testing.assert_array_equal(dev.S, host.S)
    assert dev._n_selects == host._n_selects


# ---------------------------------------------------------------------------
# vectorized billing: batch helper == per-event Python loop
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=50)
@given(st.data())
def test_batch_payload_bytes_matches_scalar_loop(data):
    shape = tuple(data.draw(
        st.lists(st.integers(1, 9), min_size=1, max_size=4),
        label="shape"))
    batch = data.draw(st.integers(1, 64), label="batch")
    dtype_bytes = data.draw(st.sampled_from([2, 4]), label="db")
    grad_down = data.draw(st.booleans(), label="gd")
    n_ev = data.draw(st.integers(0, 12), label="n_ev")
    sparse = data.draw(st.booleans(), label="sparse")
    if sparse:
        fracs = np.asarray(data.draw(
            st.lists(st.floats(0.0, 1.0, width=32), min_size=max(n_ev, 1),
                     max_size=max(n_ev, 1)), label="fracs"), np.float32)
        want = sum(split_payload_bytes(shape, batch, nnz_fraction=float(f),
                                       grad_down=grad_down,
                                       dtype_bytes=dtype_bytes)
                   for f in fracs)
        got = batch_payload_bytes(shape, batch, nnz_fracs=fracs,
                                  grad_down=grad_down,
                                  dtype_bytes=dtype_bytes)
    else:
        want = n_ev * split_payload_bytes(shape, batch,
                                          grad_down=grad_down,
                                          dtype_bytes=dtype_bytes)
        got = batch_payload_bytes(shape, batch, count=n_ev,
                                  grad_down=grad_down,
                                  dtype_bytes=dtype_bytes)
    assert got == want


@settings(deadline=None, max_examples=25)
@given(st.data())
def test_meter_ingest_round_matches_event_loop(data):
    """The vectorized ingest_round == the seed's per-event accumulation
    (client FLOPs per iteration, then per selected client payload +
    server FLOPs), byte- and flop-exact."""
    T = data.draw(st.integers(1, 4), label="T")
    k = data.draw(st.integers(1, 5), label="k")
    n = data.draw(st.integers(k, 8), label="n")
    batch = data.draw(st.integers(1, 32), label="batch")
    grad_down = data.draw(st.booleans(), label="gd")
    sparse = data.draw(st.booleans(), label="sparse")
    shape = (batch, 4, 4, 8)
    fl_c, fl_s = 1.5e6, 2.5e6
    fracs = None
    if sparse:
        rng = np.random.default_rng(data.draw(st.integers(0, 99)))
        fracs = rng.uniform(0, 1, (T, k)).astype(np.float32)

    m1 = Meter()
    m1.ingest_round(acts_shape=shape, batch=batch, n_clients=n,
                    n_iters=T, client_flops_per_example=fl_c,
                    server_flops_per_example=fl_s, nnz_fracs=fracs,
                    n_selected=k, grad_down=grad_down)
    m2 = Meter()
    for t in range(T):
        m2.add_client_flops(3 * fl_c * n * batch)
        for j in range(k):
            f = float(fracs[t, j]) if fracs is not None else None
            m2.add_payload(split_payload_bytes(shape, batch,
                                               nnz_fraction=f,
                                               grad_down=grad_down))
            m2.add_server_flops(3 * fl_s * batch)
    assert m1.bandwidth_bytes == m2.bandwidth_bytes
    assert m1.client_flops == m2.client_flops
    assert m1.server_flops == m2.server_flops


@settings(deadline=None, max_examples=20)
@given(st.data())
def test_meter_ingest_epoch_matches_sequential_rounds(data):
    R = data.draw(st.integers(1, 4), label="R")
    T = data.draw(st.integers(1, 3), label="T")
    k = data.draw(st.integers(1, 4), label="k")
    sparse = data.draw(st.booleans(), label="sparse")
    shape, batch, n = (8, 4, 4, 8), 8, 6
    fl_c, fl_s = 1.1e6, 2.2e6
    fracs = None
    if sparse:
        rng = np.random.default_rng(data.draw(st.integers(0, 99)))
        fracs = rng.uniform(0, 1, (R, T, k)).astype(np.float32)

    kw = dict(acts_shape=shape, batch=batch, n_clients=n, n_iters=T,
              client_flops_per_example=fl_c,
              server_flops_per_example=fl_s, n_selected=k)
    m1 = Meter()
    summaries = m1.ingest_epoch(n_rounds=R, nnz_fracs=fracs, **kw)
    m2 = Meter()
    want = []
    for r in range(R):
        m2.ingest_round(nnz_fracs=fracs[r] if fracs is not None else None,
                        **kw)
        want.append(m2.summary())
    assert m1.bandwidth_bytes == m2.bandwidth_bytes
    assert m1.client_flops == m2.client_flops
    assert m1.server_flops == m2.server_flops
    assert summaries == want
