"""Launcher-level units: input specs, policies, window selection,
roofline loader."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import (INPUT_SHAPES, LONG_CONTEXT_WINDOW,
                                get_config, list_archs)
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (LaunchPolicy, OPTIMIZED_OVERRIDES,
                                arch_window, default_policy, input_specs,
                                optimized_policy)


def test_input_shapes_assignment():
    assert set(INPUT_SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                                 "long_500k"}
    s = INPUT_SHAPES["train_4k"]
    assert (s.seq_len, s.global_batch, s.kind) == (4096, 256, "train")
    s = INPUT_SHAPES["long_500k"]
    assert (s.seq_len, s.global_batch, s.kind) == (524288, 1, "decode")


def test_all_archs_registered():
    archs = list_archs()
    assert len(archs) == 10
    for a in archs:
        cfg = get_config(a)
        assert cfg.source, f"{a} missing citation"
        r = cfg.reduced()
        assert r.n_layers <= 4 and r.d_model <= 512
        assert r.n_experts <= 4


def test_window_selection():
    # full-attention arch gets the documented sliding window at 500k
    assert arch_window(get_config("granite-3-8b"),
                       INPUT_SHAPES["long_500k"]) == LONG_CONTEXT_WINDOW
    # ...but not at train_4k
    assert arch_window(get_config("granite-3-8b"),
                       INPUT_SHAPES["train_4k"]) == 0
    # pure SSM never needs one
    assert arch_window(get_config("mamba2-370m"),
                       INPUT_SHAPES["long_500k"]) == 0


def test_default_policy_scaling():
    small = default_policy(get_config("olmo-1b"), INPUT_SHAPES["train_4k"])
    big = default_policy(get_config("qwen2-vl-72b"),
                         INPUT_SHAPES["train_4k"])
    assert not small.fsdp and big.fsdp
    assert big.seq_shard
    assert big.microbatch >= 2


def test_optimized_policy_overrides_apply():
    for (arch, shape), over in OPTIMIZED_OVERRIDES.items():
        pol = optimized_policy(get_config(arch), INPUT_SHAPES[shape])
        for k, v in over.items():
            assert getattr(pol, k) == v, (arch, shape, k)
    # non-hillclimbed pair falls back to baseline
    base = default_policy(get_config("olmo-1b"), INPUT_SHAPES["train_4k"])
    opt = optimized_policy(get_config("olmo-1b"), INPUT_SHAPES["train_4k"])
    assert base == opt


@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_input_specs_shapes(shape_name):
    cfg = get_config("qwen2-vl-72b")
    mesh = make_host_mesh()
    shape = INPUT_SHAPES[shape_name]
    specs = input_specs(cfg, shape, mesh)
    if shape.kind == "train":
        assert specs["tokens"].shape == (shape.global_batch, shape.seq_len)
        assert specs["labels"].dtype == jnp.int32
        assert "vision_embeds" in specs      # vlm frontend stub
        assert specs["select"].shape[0] == 1  # host mesh: 1 data slice
    elif shape.kind == "prefill":
        assert specs["tokens"].shape == (shape.global_batch, shape.seq_len)
    else:
        assert specs["token"].shape == (shape.global_batch, 1)


def test_enc_dec_specs_have_src_embeds():
    cfg = get_config("seamless-m4t-large-v2")
    mesh = make_host_mesh()
    specs = input_specs(cfg, INPUT_SHAPES["train_4k"], mesh)
    assert specs["src_embeds"].shape == (256, 4096, cfg.d_model)
    assert specs["src_embeds"].dtype == jnp.bfloat16


def test_roofline_loader_and_notes(tmp_path):
    import json
    from repro.launch import roofline
    rec = {"arch": "x", "shape": "train_4k", "mesh": "pod",
           "tag": "baseline", "t_compute": 1.0, "t_memory": 5.0,
           "t_collective": 2.0, "bottleneck": "t_memory",
           "useful_flops_ratio": 0.5,
           "collective_by_kind": {"all-gather": 10.0}}
    (tmp_path / "a.json").write_text(json.dumps(rec))
    old = roofline.ARTIFACTS
    try:
        roofline.ARTIFACTS = tmp_path
        recs = roofline.load("pod")
        assert len(recs) == 1
        assert "fuse" in roofline.note_for(recs[0])
        assert "| x | train_4k |" in roofline.md_table(recs)
    finally:
        roofline.ARTIFACTS = old


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_directory(monkeypatch, tmp_path, env_set):
    """``JAX_COMPILATION_CACHE_DIR``, when set, is the only cache
    directory (nothing else is configured); unset, the cache goes to the
    fixed, git-ignored ``.jax_cache/`` at the checkout root."""
    from pathlib import Path
    from repro.utils import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if env_set:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            root = Path(compile_cache.__file__).resolve().parents[3]
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert ".jax_cache/" in (root / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
