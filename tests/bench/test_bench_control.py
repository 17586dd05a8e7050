"""``correct`` fails where it must: the control (the plain reference one
precision lower, in the program's place) and planted faults in the
timed path.  Small sizes on the host: the harness's look for a chip is
skipped, the rest of a run is driven as on the chip, with the cells' own
limits.  Every run builds its programs anew, as a run on the chip does;
the module keeps JAX's persistent compilation cache in a directory of its
own, so a program that an earlier test compiled is read back, not
compiled again."""
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import run as brun
from bench.harness import CompileCounter, Ctx

ROOT = Path(__file__).resolve().parents[2]
DEVICE = {"platform": "cpu", "kind": "host", "count": 1}
CACHE_OPTIONS = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    """This module's own persistent compilation cache, and the worker's
    settings as they were once it is done."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = {k: getattr(jax.config, k) for k in CACHE_OPTIONS}
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    yield
    cc.reset_cache()
    for k, v in before.items():
        jax.config.update(k, v)


def _ctx(cell, seed, **sizes):
    man = brun.manifest()
    _, workload, config = brun.load_cell(man, cell)
    for k, v in sizes.items():
        (config if k in config or k == "hparams" else workload)[k] = v
    return man, Ctx(name=cell, workload=workload, config=config, seed=seed,
                    seconds=0.01, trace=False, chips=1,
                    devices=jax.devices(), t_start=time.perf_counter(),
                    compiles=CompileCounter())


def _train_ctx(seed=2**31 + 3):
    man, ctx = _ctx("train.lenet.c16", seed, clients=8,
                    examples_per_client=128, test_per_client=8)
    return man, ctx


def _serve_ctx(seed=2**31 + 5):
    man, ctx = _ctx(
        "serve.qwen2.poisson", seed, num_hidden_layers=8, hidden_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        intermediate_size=256, vocab_size=500, rate_per_s=300.0,
        max_batch=4, cache_len=96, check_tokens=60,
        prompt={"median": 24, "sigma": 0.7, "min": 8, "max": 64},
        budget={"median": 8, "min": 4, "max": 32},
        clients={"population": 64, "zipf": 1.1})
    return man, ctx


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def test_train_sound_run_is_correct():
    man, ctx = _train_ctx()
    res = brun.run_cell(ctx, man, DEVICE)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["metrics"]["train_examples_per_s"]["value"] > 0


def test_train_frozen_state_fails():
    """A step that returns its state unchanged."""
    from bench.control import frozen_state
    man, ctx = _train_ctx()
    with frozen_state():
        res = brun.run_cell(ctx, man, DEVICE)
    assert not res["correct"]
    assert res["checks"]["change1"]["value"] == pytest.approx(1.0)


def test_train_half_batch_fails():
    """Half of each client's batch left out of its loss, the mean taken
    over the rest."""
    from bench.control import half_batch
    man, ctx = _train_ctx()
    with half_batch():
        res = brun.run_cell(ctx, man, DEVICE)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("dtype,fault", [("bfloat16", None),
                                         ("float32", "half")])
def test_train_reference_in_the_programs_place_fails(dtype, fault):
    """The control (bfloat16 reference) and the half-batch fault planted
    in the reference, each read against the float32 reference."""
    from bench.drivers import train
    _, ctx = _train_ctx()
    raw = train.population(ctx)
    n = ctx.workload["check_steps"]
    steps, snaps = train.reference_candidate(ctx, raw, n, dtype=dtype,
                                             fault=fault)
    nums = train.reference_numbers(ctx, raw, steps, snaps, n)
    over = [k for k, lim in ctx.workload["limits"].items() if nums[k] > lim]
    assert over, nums


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def test_serve_sound_run_is_correct():
    man, ctx = _serve_ctx()
    res = brun.run_cell(ctx, man, DEVICE)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"]["serve_latency_p95_ms"]["value"] > 0


def test_serve_altered_token_fails(monkeypatch):
    """A token altered where the decode step produces it."""
    from repro.serve.continuous import ContinuousEngine
    orig = ContinuousEngine._decode_fn

    def altered(self, params, cache, tok, pos, gates, outbuf, gen_idx):
        tok, cache, outbuf = orig(self, params, cache, tok, pos, gates,
                                  outbuf, gen_idx)
        bad = (tok + 1) % self.cfg.vocab_size
        tok = tok.at[0].set(bad[0])
        outbuf = outbuf.at[0, gen_idx[0]].set(bad[0, 0])
        return tok, cache, outbuf

    monkeypatch.setattr(ContinuousEngine, "_decode_fn", altered)
    man, ctx = _serve_ctx()
    res = brun.run_cell(ctx, man, DEVICE)
    assert not res["correct"], res["checks"]


def test_serve_fp8_control_fails():
    """The control: at each served position, the token a float8 copy of
    the reference puts first, read in the float32 reference."""
    from bench.drivers import serve
    from bench.reference import qwen2 as ref
    man, ctx = _serve_ctx()
    import jax.numpy as jnp
    cfgj, w = ctx.config, ctx.workload
    # the served depth: float8's rounding of the residual stream adds up
    # layer by layer, as bfloat16's does in the served model
    cfgj.update(num_hidden_layers=24, hidden_size=256, head_dim=64,
                intermediate_size=512)
    params = jax.jit(lambda k: ref.init_params(cfgj, k))(
        jax.random.PRNGKey(ctx.sub_seed(3)))
    rng = np.random.default_rng(0)

    class R:                                    # served requests
        def __init__(self, i):
            self.client_id = int(rng.integers(64))
            self.prompt = rng.integers(0, 500, 40).astype(np.int32)
            self.max_new_tokens = 30
            self.output = None

    sample = [R(i) for i in range(3)]
    rows = ref.mask_rows(cfgj, jax.random.PRNGKey(ctx.sub_seed(4)),
                         [r.client_id for r in sample], w["mask_keep"])
    logits = jax.jit(lambda p, s, at, g: ref.logits_at(cfgj, p, s, at, g))
    for j, r in enumerate(sample):              # greedy f32 answers
        seq = list(r.prompt)
        g = jax.tree.map(lambda a: a[j], rows)
        for _ in range(r.max_new_tokens):
            padded = np.zeros(w["cache_len"], np.int32)
            padded[:len(seq)] = seq
            lg = logits(params, jnp.asarray(padded),
                        jnp.asarray([len(seq) - 1]), g)
            seq.append(int(jnp.argmax(lg[0])))
        r.output = np.asarray(seq[len(r.prompt):], np.int32)
    sound = serve.gaps(ctx, sample)
    control = serve.gaps(ctx, sample, quant="fp8")
    limit = w["limits"]["served_gap"]
    assert max(sound) <= limit < max(control), (sound, control)
