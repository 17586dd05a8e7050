"""End-to-end system behaviour: the paper's protocol trains and beats
its own ablations on the metered trade-off; the LM pod-scale variant
runs; serving folds masks correctly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import InputShape, get_config
from repro.core.adasplit import AdaSplitHParams, AdaSplitTrainer
from repro.data.synthetic import mixed_noniid

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def small_clients():
    return mixed_noniid(n_clients=3, n_per_client=160, n_test=60, seed=1)


def test_adasplit_learns():
    # 320 examples per client = 10 iterations per round, 70 server steps
    # over the 7 global rounds.  With 160 (35 server steps) whether the
    # bar was met hung on the init draw: jax's threefry stream changed
    # (jax_threefry_partitionable now defaults on) and seeds 0-2 reach
    # 13-42%; at 320 seeds 0-4 reach 72-98%.
    clients = mixed_noniid(n_clients=3, n_per_client=320, n_test=60, seed=1)
    cfg = get_config("lenet-cifar")
    hp = AdaSplitHParams(rounds=10, kappa=0.3, eta=0.67, batch_size=32,
                         seed=0)
    tr = AdaSplitTrainer(cfg, hp, clients)
    hist = tr.train(eval_every=10)
    acc = hist[-1]["accuracy"]
    assert acc > 20.0, f"AdaSplit failed to learn: {acc}"
    # two-phase schedule respected
    phases = [h["phase"] for h in hist]
    assert phases[0] == "local" and phases[-1] == "global"
    # bandwidth only spent in global phase
    assert hist[1]["bandwidth_gb"] == 0.0
    assert hist[-1]["bandwidth_gb"] > 0.0


def test_adasplit_kappa_tradeoff(small_clients):
    """Higher kappa (longer local phase) => strictly less bandwidth —
    the paper's Table 4 relationship."""
    cfg = get_config("lenet-cifar")
    bw = {}
    for kappa in (0.34, 0.67):
        hp = AdaSplitHParams(rounds=3, kappa=kappa, batch_size=32, seed=0)
        tr = AdaSplitTrainer(cfg, hp, small_clients)
        tr.train(eval_every=10)
        bw[kappa] = tr.meter.bandwidth_gb
    assert bw[0.67] < bw[0.34]


def test_adasplit_eta_tradeoff(small_clients):
    """Fewer selected clients (lower eta) => less bandwidth."""
    cfg = get_config("lenet-cifar")
    bw = {}
    for eta in (0.34, 1.0):
        hp = AdaSplitHParams(rounds=2, kappa=0.0, eta=eta, batch_size=32,
                             seed=0)
        tr = AdaSplitTrainer(cfg, hp, small_clients)
        tr.train(eval_every=10)
        bw[eta] = tr.meter.bandwidth_gb
    assert bw[0.34] < bw[1.0]


def test_lm_adasplit_trainer_runs():
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import LaunchPolicy
    from repro.launch.train import LMAdaSplitTrainer
    cfg = get_config("qwen2-0.5b").reduced()
    mesh = make_host_mesh()
    shape = InputShape("t", 64, 8, "train")
    pol = LaunchPolicy(fsdp=False, microbatch=1, seq_shard=False)
    tr = LMAdaSplitTrainer(cfg, mesh, shape, pol, kappa=0.5)
    hist = tr.run(4)
    assert len(hist) == 4
    assert hist[0]["phase"] == "local" and hist[-1]["phase"] == "global"
    assert np.isfinite(hist[-1]["ce"]) and hist[-1]["ce"] > 0
    assert np.isfinite(hist[-1]["l_client"])
    assert hist[-1]["bandwidth_gb"] > 0


def test_serve_session_with_folded_mask():
    from repro.core import masks as masks_mod
    from repro.launch.serve import serve_session
    from repro.launch.steps import init_serve_params
    cfg = get_config("olmo-1b").reduced()
    params = init_serve_params(cfg, jax.random.PRNGKey(0))
    masks = masks_mod.init_unit_masks(cfg, 2)
    params = dict(params)
    params["server"] = masks_mod.fold_unit_masks(cfg, params["server"],
                                                 masks, 0)
    prompts = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 12)), jnp.int32)
    out = serve_session(cfg, params, prompts, 4)
    assert out.shape == (2, 4)
    assert bool((out >= 0).all()) and bool((out < cfg.vocab_size).all())
