"""Compile-only rehearsals of the training path's Pallas kernels for a TPU
v5e that is described, not attached: the chip's compiler runs here and
refuses what it would refuse on the chip (block shapes off the (8, 128)
tiling, too much fast memory).  Nothing executes.  Each test asserts the
kernel is in the compiled program as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.models import lenet

CFG = get_config("lenet-cifar")
BATCH = 32


def _lenet_conv_blocks():
    """(H, Cin, Cout) of every 5x5 conv block of the published LeNet."""
    out, cin, h = [], 3, CFG.image_size
    for c in CFG.conv_channels:
        out.append((h, cin, c))
        cin, h = c, h // 2
    return out


BLOCKS = _lenet_conv_blocks()


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a cache entry compiled for a described chip cannot be read
    back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:        # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _kernels(fn, *args):
    from repro.kernels.ops import compiled_kernels
    return compiled_kernels(jax.jit(fn).lower(*args).compile().as_text())


def _panels(one_chip, C, h, cin, cout):
    """The stacked conv's GEMM operands: (C, B*H*W, 25*Cin) patches and
    (C, 25*Cin, Cout) filter panels."""
    kd = 25 * cin
    return (jax.ShapeDtypeStruct((C, BATCH * h * h, kd), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((C, kd, cout), jnp.float32,
                                 sharding=one_chip))


@pytest.mark.parametrize("C", [5, 16])
@pytest.mark.parametrize("block", range(len(BLOCKS)))
def test_panel_gemm_compiles_for_v5e(one_chip, C, block):
    from repro.kernels.client_conv import panel_gemm
    a, b = _panels(one_chip, C, *BLOCKS[block])
    assert "panel_gemm" in _kernels(panel_gemm, a, b)


@pytest.mark.parametrize("C", [5, 16])
@pytest.mark.parametrize("block", range(len(BLOCKS)))
def test_fused_epilogue_compiles_for_v5e(one_chip, C, block):
    from repro.kernels.client_conv import panel_gemm_fused
    a, b = _panels(one_chip, C, *BLOCKS[block])
    bias = jax.ShapeDtypeStruct((C, BLOCKS[block][2]), jnp.float32,
                                sharding=one_chip)
    assert "panel_gemm_bias_relu" in _kernels(panel_gemm_fused, a, b, bias)


def _sds(tree, one_chip):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), tree)


def test_fused_adam_compiles_for_v5e_server(one_chip):
    """The server optimizer step over every LeNet server leaf."""
    from repro.kernels.masked_adam import fused_adam_update
    from repro.optim.adam import adam_init
    sp = jax.eval_shape(lambda: lenet.init_server_params(
        CFG, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(adam_init, sp)
    got = _kernels(lambda p, g, o: fused_adam_update(p, g, o, lr=1e-3),
                   _sds(sp, one_chip), _sds(sp, one_chip),
                   _sds(opt, one_chip))
    assert "masked_adam" in got


@pytest.mark.parametrize("C", [5, 16])
def test_fused_adam_compiles_for_v5e_masks(one_chip, C):
    """The per-client mask update as the global step runs it: vmapped
    over the S selected clients, each with its own Adam step count, so
    the kernel's scalar-prefetch operand is batched."""
    from repro.core.masks import init_lenet_unit_masks
    from repro.kernels.masked_adam import fused_adam_update
    from repro.optim.adam import adam_init
    S = max(1, round(0.6 * C))
    m = jax.eval_shape(lambda: init_lenet_unit_masks(CFG, S))
    opt = jax.eval_shape(adam_init, m)
    opt["step"] = jax.ShapeDtypeStruct((S,), jnp.int32)
    step = jax.vmap(lambda p, g, o: fused_adam_update(p, g, o, lr=1e-3))
    assert "masked_adam" in _kernels(step, _sds(m, one_chip),
                                     _sds(m, one_chip), _sds(opt, one_chip))


def test_decode_attention_grouped_for_v5e(one_chip):
    """One Qwen2-0.5B decode layer as the serving engine runs it (64
    slots, per-slot positions, a 1280-row bfloat16 cache, 14 query heads
    on 2 KV heads of 64) compiles with little scratch and never
    materialises a float32 K/V (or score) array repeated to 14 heads
    over the cache's rows: attention reads the bfloat16 cache as it is."""
    import re
    from repro.models import attention as attn
    cfg = get_config("qwen2-0.5b")
    B, L, bf = 64, 1280, jnp.bfloat16
    p = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, bf, sharding=one_chip),
        jax.eval_shape(lambda: attn.attention_init(jax.random.PRNGKey(0),
                                                   cfg)))
    x = jax.ShapeDtypeStruct((B, 1, cfg.d_model), bf, sharding=one_chip)
    cache = {n: jax.ShapeDtypeStruct((B, L, cfg.n_kv_heads, cfg.head_dim),
                                     bf, sharding=one_chip) for n in "kv"}
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, x, c, pos: attn.attn_decode(
        p, x, c, pos, cfg)).lower(p, x, cache, pos).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2**20
    dims = [s.split(",") for s in
            re.findall(r"f32\[([0-9,]+)\]", compiled.as_text())]
    assert not [d for d in dims if str(cfg.n_heads) in d and str(L) in d]
