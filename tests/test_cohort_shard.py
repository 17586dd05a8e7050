"""Cohort-sharded training (``shard_clients=True``) differentials.

The stacked client axis C is partitioned across the mesh's ``data``
axis with ``shard_map`` (C/ndev clients per device); selection is a
local ``ucb_advantage`` + all-gather + replicated top-k and the global
step runs replicated over the all-gathered selected cohort, so the
8-device run must reproduce the 1-device scan drivers:

* selections (the orchestrator's S history) and meter byte totals:
  EXACT — the gathered advantage vector and the billing counts are
  elementwise identical across device counts;
* CE history / final params: fp32 tolerance — the per-shard client
  step batches C/ndev (not C) conv panels through the backend GEMM,
  whose blocking at different batch widths may perturb the last bit.

The in-process tests need emulated host devices and SKIP on a single
device — CI runs them in the ``test-multidevice`` lane under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  The
subprocess test at the bottom exercises the same differential from any
environment (slow lane).
"""
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.adasplit import AdaSplitHParams, AdaSplitTrainer
from repro.data.synthetic import mixed_noniid

CFG = get_config("lenet-cifar")

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 host devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def clients8():
    return mixed_noniid(n_clients=8, n_per_client=32, n_test=16, seed=0)


def _train(clients, **kw):
    defaults = dict(rounds=3, kappa=0.34, batch_size=8, seed=7)
    defaults.update(kw)
    tr = AdaSplitTrainer(CFG, AdaSplitHParams(**defaults), clients)
    tr.train(eval_every=10)
    return tr


def _max_leaf_diff(a, b):
    return max(float(jnp.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _assert_sharded_matches(sh, ref, *, param_tol=1e-4, byte_tol=0.0):
    assert sh._shard, "sharding did not engage"
    assert not ref._shard
    # selections: bit-identical (all-gathered advantages == 1-device)
    np.testing.assert_array_equal(sh.orch.S, ref.orch.S)
    assert sh.orch._n_selects == ref.orch._n_selects
    # CE history: fp32 tolerance (per-shard GEMM blocking)
    np.testing.assert_allclose(sh.orch.L, ref.orch.L, rtol=1e-5,
                               atol=1e-5)
    # protocol meters: layout-invariant (exact when act_l1 is off;
    # nnz truncation boundaries allow a hair of slack otherwise)
    if byte_tol:
        np.testing.assert_allclose(sh.meter.bandwidth_bytes,
                                   ref.meter.bandwidth_bytes,
                                   rtol=byte_tol)
    else:
        assert sh.meter.bandwidth_bytes == ref.meter.bandwidth_bytes
    assert sh.meter.client_flops == ref.meter.client_flops
    assert sh.meter.server_flops == ref.meter.server_flops
    # sharding is the ONLY run paying interconnect
    assert ref.meter.interconnect_bytes == 0.0
    assert sh.meter.interconnect_bytes > 0.0
    # final params: fp32 tolerance
    assert _max_leaf_diff(sh.server_params, ref.server_params) < param_tol
    assert _max_leaf_diff(sh.client_params, ref.client_params) < param_tol
    assert _max_leaf_diff(sh.masks, ref.masks) < param_tol
    # history records line up (phases, rounds, cumulative bandwidth)
    assert len(sh.history) == len(ref.history)
    for h_s, h_r in zip(sh.history, ref.history):
        assert h_s["round"] == h_r["round"]
        assert h_s["phase"] == h_r["phase"]
        assert h_s["bandwidth_gb"] == pytest.approx(h_r["bandwidth_gb"],
                                                    rel=byte_tol or 1e-12)


@pytest.fixture(scope="module")
def round_ref(clients8):
    return _train(clients8)


# ---------------------------------------------------------------------------
# differential: 8-device shard_clients == 1-device scan drivers
# ---------------------------------------------------------------------------


@multidevice
def test_round_scan_sharded_matches_single_device(clients8, round_ref):
    sh = _train(clients8, shard_clients=True)
    _assert_sharded_matches(sh, round_ref)


@multidevice
@pytest.mark.parametrize("chunk", [0, 1])
def test_epoch_scan_sharded_matches_single_device(clients8, round_ref,
                                                  chunk):
    """The acceptance differential: 8-emulated-device shard_clients
    epoch run reproduces the 1-device ``epoch_scan`` driver (which is
    itself bit-identical to the per-round reference)."""
    sh = _train(clients8, shard_clients=True, epoch_scan=True,
                epoch_chunk_rounds=chunk)
    _assert_sharded_matches(sh, round_ref)


@multidevice
@pytest.mark.parametrize("kw, byte_tol", [
    (dict(server_grad_to_client=True), 0.0),
    (dict(mask_mode="per_scalar"), 0.0),
    (dict(act_l1=1e-1, act_threshold=0.5), 1e-4),
], ids=["joint", "per_scalar", "act_l1"])
def test_sharded_variants_match(clients8, kw, byte_tol):
    """All-global runs across the joint / per-scalar / activation-
    sparsified configs (the joint path moves client params through the
    all-gather + shard-local scatter too)."""
    ref = _train(clients8, kappa=0.0, **kw)
    sh = _train(clients8, kappa=0.0, shard_clients=True, **kw)
    # joint accumulates client+server grads through more fp32 steps
    tol = 1e-3 if kw.get("server_grad_to_client") else 1e-4
    _assert_sharded_matches(sh, ref, param_tol=tol, byte_tol=byte_tol)


@multidevice
def test_sharded_eval_matches(clients8, round_ref):
    sh = _train(clients8, shard_clients=True)
    assert sh.evaluate() == pytest.approx(round_ref.evaluate(), abs=1e-3)


# ---------------------------------------------------------------------------
# sharded ucb_select == replicated reference (property)
# ---------------------------------------------------------------------------


@multidevice
def test_sharded_ucb_select_property():
    """shard_map'd selection (local advantage -> all-gather ->
    replicated top-k) is BITWISE the host ``ucb_select`` for random
    advantage states, including near-tie blocks the keyed jitter has
    to break."""
    from jax.sharding import PartitionSpec as P
    from repro.core.orchestrator import (ucb_advantage, ucb_select,
                                         ucb_select_from_advantage)
    from repro.launch.mesh import make_cohort_mesh

    mesh = make_cohort_mesh(8)
    n, k = 32, 19
    state_specs = {"l_disc": P("data"), "s_disc": P("data"),
                   "last": P("data"), "prev": P("data"), "t": P()}

    def sharded_select(state, key):
        adv = jax.lax.all_gather(ucb_advantage(state), "data", tiled=True)
        return ucb_select_from_advantage(adv, k, key)

    fn = jax.jit(jax.shard_map(sharded_select, mesh=mesh,
                               in_specs=(state_specs, P()), out_specs=P(),
                               check_vma=False))
    rng = np.random.default_rng(0)
    for case in range(8):
        l = rng.normal(50, 40, n).astype(np.float32)
        if case % 2:          # force exact ties across shard boundaries
            l[:] = l[0]
        state = {"l_disc": jnp.asarray(l),
                 "s_disc": jnp.asarray(
                     rng.uniform(0.5, 2.0, n).astype(np.float32)),
                 "last": jnp.asarray(l), "prev": jnp.asarray(l),
                 "t": jnp.asarray(2 + case, jnp.int32)}
        if case % 2:
            state["s_disc"] = jnp.ones((n,), jnp.float32)
        key = jax.random.PRNGKey(case)
        np.testing.assert_array_equal(np.asarray(fn(state, key)),
                                      np.asarray(ucb_select(state, k, key)))


# ---------------------------------------------------------------------------
# fallbacks
# ---------------------------------------------------------------------------


@multidevice
def test_non_divisible_cohort_falls_back(round_ref):
    """6 clients on 8 devices: warn, run unsharded, still train."""
    clients6 = mixed_noniid(n_clients=6, n_per_client=32, n_test=16,
                            seed=0)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        tr = AdaSplitTrainer(
            CFG, AdaSplitHParams(rounds=1, kappa=0.0, batch_size=8,
                                 shard_clients=True), clients6)
    assert not tr._shard
    assert any("divisible" in str(x.message) for x in w)
    hist = tr.train(eval_every=10)
    assert hist[-1]["bandwidth_gb"] > 0
    assert tr.meter.interconnect_bytes == 0.0


def test_single_device_shard_flag_is_noop(tiny_clients):
    """shard_clients on a 1-device mesh degrades to the plain path
    (this is the case the default CI lane exercises)."""
    from repro.launch.mesh import make_cohort_mesh
    tr = AdaSplitTrainer(
        CFG, AdaSplitHParams(rounds=1, kappa=0.0, batch_size=8,
                             shard_clients=True), tiny_clients,
        mesh=make_cohort_mesh(1))
    assert not tr._shard
    hist = tr.train(eval_every=10)
    assert hist[-1]["bandwidth_gb"] > 0


def test_shard_without_scan_drivers_falls_back(tiny_clients):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        tr = AdaSplitTrainer(
            CFG, AdaSplitHParams(rounds=1, round_scan=False,
                                 shard_clients=True, batch_size=8),
            tiny_clients)
    assert not tr._shard
    assert any("scan drivers" in str(x.message) for x in w)


# ---------------------------------------------------------------------------
# subprocess differential (runs from ANY environment; slow lane)
# ---------------------------------------------------------------------------


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import numpy as np, jax
from repro.configs.base import get_config
from repro.core.adasplit import AdaSplitHParams, AdaSplitTrainer
from repro.data.synthetic import mixed_noniid

clients = mixed_noniid(n_clients=8, n_per_client=32, n_test=16, seed=0)
def train(**kw):
    hp = AdaSplitHParams(rounds=3, kappa=0.34, batch_size=8, seed=7, **kw)
    tr = AdaSplitTrainer(get_config("lenet-cifar"), hp, clients)
    tr.train(eval_every=10)
    return tr
ref = train(epoch_scan=True)
sh = train(epoch_scan=True, shard_clients=True)
assert sh._shard and jax.device_count() == 8
np.testing.assert_array_equal(sh.orch.S, ref.orch.S)
np.testing.assert_allclose(sh.orch.L, ref.orch.L, rtol=1e-5, atol=1e-5)
assert sh.meter.bandwidth_bytes == ref.meter.bandwidth_bytes
d = max(float(abs(np.asarray(a) - np.asarray(b)).max()) for a, b in
        zip(jax.tree.leaves(sh.server_params),
            jax.tree.leaves(ref.server_params)))
assert d < 1e-4, d
print("COHORT-SHARD-OK")
"""


@pytest.mark.slow
def test_cohort_shard_differential_subprocess():
    """The 8-device epoch differential from a 1-device environment:
    the XLA device-count override must not leak into this process."""
    r = subprocess.run([sys.executable, "-c", SUBPROC],
                       capture_output=True, text=True, timeout=1800)
    assert "COHORT-SHARD-OK" in r.stdout, r.stdout + r.stderr
