"""Sharding rules + launch steps: spec structure, divisibility fallbacks,
and an in-process (1,1)-mesh lower/compile integration check.  The real
multi-device partitioning is exercised by the subprocess test at the
bottom (the 512-device override must never leak into this process)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config, InputShape
from repro.launch.steps import (LaunchPolicy, build_step, default_policy,
                                init_train_state, train_state_specs)
from repro.sharding.rules import MeshAxes
from repro.launch.mesh import make_host_mesh


class FakeAxes(MeshAxes):
    pass


AX = MeshAxes(model="model", data=("data",), model_size=16, data_size=16)


def _server_specs(arch):
    from repro.models import transformer as tfm
    from repro.sharding.rules import server_pspecs
    cfg = get_config(arch)
    abstract = jax.eval_shape(
        lambda: tfm.init_server_params(cfg, jax.random.PRNGKey(0)))
    return cfg, abstract, server_pspecs(cfg, abstract, AX)


def test_attention_tp_specs():
    cfg, params, specs = _server_specs("phi3-mini-3.8b")
    seg = specs["segments"][0][0]
    assert seg["mixer"]["wq"][-1] == "model"
    assert seg["mixer"]["wo"][-2] == "model"
    assert seg["ffn"]["w_gate"][-1] == "model"
    assert seg["ffn"]["w_down"][-2] == "model"
    assert specs["lm_head"]["table"][0] == "model"
    # norms replicated
    assert all(s is None for s in seg["norm1"]["scale"])


def test_qwen2_small_heads_fall_back_to_replicated():
    cfg, params, specs = _server_specs("qwen2-0.5b")
    seg = specs["segments"][0][0]
    # 14 heads % 16 != 0 -> attention replicated
    assert all(s is None for s in seg["mixer"]["wq"])
    # but MLP still sharded (4864 % 16 == 0)
    assert seg["ffn"]["w_gate"][-1] == "model"


def test_moe_expert_parallel_specs():
    cfg, params, specs = _server_specs("qwen3-moe-30b-a3b")
    moe_seg = None
    for seg_spec, seg_par in zip(specs["segments"], params["segments"]):
        for j in range(len(seg_spec)):
            if "ffn" in seg_spec[j] and "w_gate" in seg_spec[j]["ffn"] \
                    and seg_par[j]["ffn"]["w_gate"].ndim == 4:
                moe_seg = seg_spec[j]
    assert moe_seg is not None
    # (n_rep, E, D, F): experts on model
    assert moe_seg["ffn"]["w_gate"][1] == "model"
    assert all(s is None for s in moe_seg["ffn"]["router"])


def test_mamba_tp_specs():
    cfg, params, specs = _server_specs("mamba2-370m")
    seg = specs["segments"][0][0]
    assert seg["mixer"]["in_proj"][-1] == "model"
    assert seg["mixer"]["out_proj"][-2] == "model"


def test_fsdp_adds_data_axis():
    from repro.models import transformer as tfm
    from repro.sharding.rules import server_pspecs
    cfg = get_config("qwen2-vl-72b")
    abstract = jax.eval_shape(
        lambda: tfm.init_server_params(cfg, jax.random.PRNGKey(0)))
    specs = server_pspecs(cfg, abstract, AX, fsdp=True)
    seg = specs["segments"][0][0]
    flat = [a for s in seg["mixer"]["wq"] if s is not None
            for a in ((s,) if isinstance(s, str) else s)]
    assert "data" in flat and "model" in flat
    # never the scan dim
    assert seg["mixer"]["wq"][0] is None


def test_opt_specs_zero_shard():
    from repro.sharding.rules import opt_pspecs, server_pspecs
    from repro.models import transformer as tfm
    cfg = get_config("granite-3-8b")
    abstract = jax.eval_shape(
        lambda: tfm.init_server_params(cfg, jax.random.PRNGKey(0)))
    pspecs = server_pspecs(cfg, abstract, AX, fsdp=False)
    ospecs = opt_pspecs(pspecs, abstract, AX, zero=True)
    mu = ospecs["mu"]["segments"][0][0]["mixer"]["wq"]
    flat = [a for s in mu if s is not None
            for a in ((s,) if isinstance(s, str) else s)]
    assert "data" in flat  # ZeRO: moments sharded over data too


def test_train_state_spec_tree_matches_state():
    cfg = get_config("qwen2-0.5b").reduced()
    pol = LaunchPolicy(microbatch=1)
    state = jax.eval_shape(
        lambda: init_train_state(cfg, 4, pol, jax.random.PRNGKey(0)))
    mesh = make_host_mesh()
    specs = train_state_specs(cfg, state, mesh, pol)
    # same tree structure
    jax.tree.map(lambda a, b: None, state, specs)
    # every spec rank <= leaf rank
    def check(leaf, spec):
        assert len(spec) <= leaf.ndim, (leaf.shape, spec)
    jax.tree.map(check, state, specs)


@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_build_step_lowers_on_host_mesh(kind):
    cfg = get_config("olmo-1b").reduced()
    mesh = make_host_mesh()
    # batch must divide across the data axis — the host mesh spans
    # however many devices exist (8 in the multi-device CI lane)
    batch = max(4, 2 * mesh.shape["data"])
    shape = InputShape("t", 64, batch, kind)
    with mesh:
        fn, args = build_step(cfg, mesh, shape,
                              LaunchPolicy(fsdp=False, microbatch=1,
                                           seq_shard=False))
        jax.jit(fn).lower(*args).compile()


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax
from repro.configs.base import get_config, InputShape
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_step, LaunchPolicy
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_config("qwen3-moe-30b-a3b").reduced()
pol = LaunchPolicy(fsdp=True, microbatch=2, seq_shard=True)
for kind, B, S in (("train", 64, 64), ("decode", 64, 64)):
    with mesh:
        fn, args = build_step(cfg, mesh, InputShape("x", S, B, kind), pol)
        c = jax.jit(fn).lower(*args).compile()
        txt = c.as_text()
        assert any(k in txt for k in ("all-reduce", "all-gather",
                                      "all-to-all", "collective-permute")), \
            "no collectives in a multi-pod compile?!"
print("MULTIPOD-OK")
"""


def test_multipod_mesh_partitions_subprocess():
    """3-axis (pod, data, model) mesh really partitions: run in a
    subprocess so the device-count override can't pollute this one."""
    r = subprocess.run([sys.executable, "-c", SUBPROC],
                       capture_output=True, text=True, timeout=900)
    assert "MULTIPOD-OK" in r.stdout, r.stdout + r.stderr


KNOBS_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax
from repro.configs.base import get_config, InputShape
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_step, LaunchPolicy
mesh = make_mesh((4, 2), ("data", "model"))
shape = InputShape("x", 64, 32, "train")
# every hillclimb knob must lower+compile (EXPERIMENTS.md §Perf configs)
for arch, kw in [
    ("qwen2-0.5b", dict(attn_batch_shard=True)),
    ("deepseek-moe-16b", dict(seq_shard=False, microbatch=4,
                              moe_batch_pin=True)),
    ("qwen2-vl-72b", dict(attn_head_pin=True, microbatch=4)),
    ("qwen2-vl-72b", dict(attn_seq_shard=True)),
]:
    cfg = get_config(arch).reduced()
    pol = LaunchPolicy(fsdp=True, **kw)
    with mesh:
        fn, args = build_step(cfg, mesh, shape, pol)
        jax.jit(fn).lower(*args).compile()
print("KNOBS-OK")
"""


def test_perf_knobs_compile_subprocess():
    r = subprocess.run([sys.executable, "-c", KNOBS_SUBPROC],
                       capture_output=True, text=True, timeout=900)
    assert "KNOBS-OK" in r.stdout, r.stdout + r.stderr


NUMERICS_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.configs.base import get_config, InputShape
from repro.launch.mesh import make_mesh
from repro.launch.steps import (build_step, init_train_state,
                                train_state_specs, LaunchPolicy)
mesh = make_mesh((4, 2), ("data", "model"))
shape = InputShape("t", 64, 16, "train")
cfg = get_config("qwen2-0.5b").reduced()
rng = np.random.default_rng(0)
batch = {
    "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (16, 64)),
                          jnp.int32),
    "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (16, 64)),
                          jnp.int32),
    "seq_class": jnp.asarray(rng.integers(0, 4, (16,)), jnp.int32),
    "select": jnp.ones((4,), jnp.float32),
}
results = {}
for name, kw in [("baseline", {}),
                 ("attn_batch_shard", dict(attn_batch_shard=True)),
                 ("attn_head_pin", dict(attn_head_pin=True))]:
    pol = LaunchPolicy(fsdp=False, microbatch=1, seq_shard=False, **kw)
    with mesh:
        fn, _ = build_step(cfg, mesh, shape, pol)
        state = init_train_state(cfg, 4, pol, jax.random.PRNGKey(0))
        specs = train_state_specs(cfg, state, mesh, pol)
        state = jax.tree.map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
            state, specs)
        _, m = jax.jit(fn)(state, batch)
        results[name] = (float(m["ce"]), float(m["l_client"]))
base = results["baseline"]
for k, v in results.items():
    assert abs(v[0] - base[0]) < 2e-2 and abs(v[1] - base[1]) < 2e-2, \
        (k, v, base)
print("NUMERICS-OK")
"""


def test_optimized_shardings_numerically_consistent_subprocess():
    """§Perf pins are pure layout: losses must match the baseline."""
    r = subprocess.run([sys.executable, "-c", NUMERICS_SUBPROC],
                       capture_output=True, text=True, timeout=1200)
    assert "NUMERICS-OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# cohort (vision-pytree) rules: leading-C sharding + divisibility fallback
# ---------------------------------------------------------------------------


def _vision_cohort_tree(n):
    import jax.numpy as jnp
    from repro.core.orchestrator import ucb_init
    return {
        "client": {"blocks": [{"w": jnp.zeros((n, 5, 5, 3, 6)),
                               "b": jnp.zeros((n, 6))}]},
        "proj": {"w1": jnp.zeros((n, 256, 128)), "b1": jnp.zeros((n, 128))},
        "masks": {"blocks": [jnp.zeros((n, 16))],
                  "fc1": jnp.zeros((n, 120))},
        "step": jnp.zeros((n,), jnp.int32),
        "ucb": ucb_init(n),
    }


def test_cohort_pspecs_vision_tree():
    from repro.sharding.rules import cohort_pspecs
    ax = MeshAxes(model=None, data=("data",), model_size=1, data_size=8)
    tree = _vision_cohort_tree(32)
    specs = cohort_pspecs(tree, ax, cohort_size=32)
    # every leading-C leaf sharded on data, trailing dims replicated
    w = specs["client"]["blocks"][0]["w"]
    assert w[0] == "data" and all(s is None for s in w[1:])
    assert specs["step"][0] == "data"
    assert specs["ucb"]["l_disc"][0] == "data"
    # the scalar UCB counter replicates
    assert specs["ucb"]["t"] == P()


def test_cohort_pspecs_divisibility_fallback():
    from repro.sharding.rules import cohort_pspecs
    ax = MeshAxes(model=None, data=("data",), model_size=1, data_size=8)
    # 12 % 8 != 0 -> every leaf replicated (must-always-lower fallback)
    specs = cohort_pspecs(_vision_cohort_tree(12), ax, cohort_size=12)
    assert all(s == P() for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))
    # cohort_size guard: leaves whose dim 0 is NOT the cohort replicate
    mixed = {"coh": jnp.zeros((8, 4)), "other": jnp.zeros((4, 8))}
    specs = cohort_pspecs(mixed, ax, cohort_size=8)
    assert specs["coh"][0] == "data" and specs["other"] == P()
    # 1-device mesh: everything replicated
    ax1 = MeshAxes(model=None, data=("data",), model_size=1, data_size=1)
    specs = cohort_pspecs(_vision_cohort_tree(8), ax1, cohort_size=8)
    assert all(s == P() for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P)))


@pytest.mark.parametrize("ndev", [1, 2, 8])
def test_mesh_axes_from_mesh_device_counts(ndev):
    """MeshAxes.from_mesh over 1/2/8-device cohort meshes (AbstractMesh:
    no real devices needed — shape/axis metadata only)."""
    from jax.sharding import AbstractMesh
    ax = MeshAxes.from_mesh(AbstractMesh((ndev,), ("data",)))
    assert ax.data == ("data",) and ax.data_size == ndev
    assert ax.model is None and ax.model_size == 1
    assert ax.data_spec == "data"
    ax2 = MeshAxes.from_mesh(AbstractMesh((ndev, 2), ("data", "model")))
    assert ax2.data_size == ndev and ax2.model_size == 2


def test_staged_cohort_spec():
    from repro.sharding.rules import staged_cohort_spec
    ax = MeshAxes(model=None, data=("data",), model_size=1, data_size=8)
    assert staged_cohort_spec(ax, 6, cohort_dim=1) == P(None, "data",
                                                        *[None] * 4)
    assert staged_cohort_spec(ax, 7, cohort_dim=2) == P(None, None,
                                                        "data",
                                                        *[None] * 4)


def test_ucb_select_from_advantage_is_select():
    """The replicated half of sharded selection: feeding the full
    advantage vector through ``ucb_select_from_advantage`` IS
    ``ucb_select`` (hypothesis over random UCB states)."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis "
        "(pip install -r requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st
    from repro.core.orchestrator import (ucb_advantage, ucb_init,
                                         ucb_select,
                                         ucb_select_from_advantage,
                                         ucb_update)
    import numpy as np

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(4, 24),
           st.data())
    def prop(seed, n, data):
        rng = np.random.default_rng(seed)
        state = ucb_init(n)
        for _ in range(data.draw(st.integers(0, 3))):
            mask = (rng.random(n) < 0.5).astype(np.float32)
            state = ucb_update(state, jnp.asarray(mask),
                               jnp.asarray(rng.random(n, np.float32) * 10),
                               gamma=0.87)
        k = data.draw(st.integers(1, n))
        key = jax.random.PRNGKey(seed)
        np.testing.assert_array_equal(
            np.asarray(ucb_select(state, k, key)),
            np.asarray(ucb_select_from_advantage(
                ucb_advantage(state), k, key)))

    prop()
