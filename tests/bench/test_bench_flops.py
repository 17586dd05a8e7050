"""Operations and bytes against hand counts, and the peak table."""
import json
from pathlib import Path

import pytest

from bench import flops

ROOT = Path(__file__).resolve().parents[2]


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_lenet_counts_by_hand():
    cfg = _cfg("lenet-cifar")
    assert flops.lenet_split(cfg) == 1
    assert flops.lenet_acts_dim(cfg) == 16 * 16 * 6
    # client: 5x5x3 -> 6 conv on 32x32, 1536 -> 128 -> 64 head, q q^T
    client = 2 * 32 * 32 * 75 * 6 + 2 * (1536 * 128 + 128 * 64) + 2 * 32 * 64
    assert flops.lenet_client_fwd_flops(cfg) == client
    server = (2 * 16 * 16 * 150 * 16 + 2 * 8 * 8 * 400 * 32
              + 2 * 4 * 4 * 800 * 64 + 2 * 2 * 2 * 1600 * 64
              + 2 * (64 * 120 + 120 * 84 + 84 * 10))
    assert flops.lenet_server_fwd_flops(cfg) == server
    local = flops.lenet_round_flops(cfg, clients=16, iters=31, batch=32,
                                    selected=10, global_phase=False)
    glob = flops.lenet_round_flops(cfg, clients=16, iters=31, batch=32,
                                   selected=10, global_phase=True)
    assert local == 3 * client * 16 * 31 * 32
    assert glob - local == 3 * server * 10 * 31 * 32


def test_panel_gemm_from_hlo_text():
    """The kernel's padded operands name the conv block; the cost is the
    block's own GEMM."""
    cfg = _cfg("lenet-cifar")
    panels = flops.lenet_conv_panels(cfg)
    assert panels == [(75, 6), (150, 16), (400, 32), (800, 64), (1600, 64)]
    text = ("%jvp_panel_gemm_.7 = f32[16,32768,128]{2,1,0} custom-call("
            "f32[16,32768,128]{2,1,0} %a, f32[16,128,128]{2,1,0} %b), "
            'custom_call_target="tpu_custom_call"')
    fl, nb = flops.panel_gemm_cost(text, panels)
    assert fl == 2 * 16 * 32768 * 75 * 6
    assert nb == 4 * (16 * 32768 * 75 + 16 * 75 * 6 + 16 * 32768 * 6)
    server = text.replace("16,32768,128", "1,81920,256").replace(
        "16,128,128", "1,256,128")
    fl, _ = flops.panel_gemm_cost(server, panels)
    assert fl == 2 * 81920 * 150 * 16
    # under a vmap over clients, with the server's panel shared
    evals = ("%vmap_panel_gemm_.6 = f32[16,1,51200,128]{3,2,1,0} custom-call("
             "f32[16,1,51200,256]{3,2,1,0} %a, f32[1,256,128]{2,1,0} %b), "
             'custom_call_target="tpu_custom_call"')
    fl, nb = flops.panel_gemm_cost(evals, panels)
    assert fl == 2 * 16 * 51200 * 150 * 16
    assert nb == 4 * (16 * 51200 * 150 + 150 * 16 + 16 * 51200 * 16)
    assert flops.panel_gemm_cost(text.replace(",128]{2,1,0} %a",
                                              ",384]{2,1,0} %a"),
                                 panels) is None
    assert flops.panel_gemm_cost("%custom-call.1 = ()", panels) is None


def test_elementwise_bytes_from_hlo_text():
    text = ("(f32[64,256]{1,0}, f32[64,256]{1,0}) custom-call(s32[2]{0} %s,"
            " f32[64,256]{1,0} %p, bf16[64,256]{1,0} %g), custom_call_target="
            '"tpu_custom_call", operand_layout_constraints={s32[2]{0}, '
            "f32[64,256]{1,0}, bf16[64,256]{1,0}}")
    fl, nb = flops.elementwise_cost(text, flops_per_element=2.0)
    assert nb == 4 * 64 * 256 * 3 + 2 * 64 * 256 + 4 * 2
    assert fl == 2.0 * 64 * 256


def test_qwen2_counts_by_hand():
    cfg = _cfg("qwen2-0.5b")
    layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert flops.lm_layer_matmul_params(cfg) == layer
    assert flops.lm_matmul_params(cfg) == 24 * layer + 151936 * 896
    assert flops.lm_kv_bytes_per_token(cfg) == 12288
    assert flops.lm_decode_step_bytes(cfg, 100) == \
        2 * (24 * layer + 151936 * 896) + 100 * 12288
    assert flops.lm_prefill_flops(cfg, 3) == \
        3 * 2 * (24 * layer + 151936 * 896) + 4 * 14 * 64 * 24 * 6


def test_peaks_refuse_an_unknown_chip():
    pk = flops.peaks("TPU v5 lite")
    assert pk["flops_bf16_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
    assert flops.roofline_seconds(197e12, 0.0, pk) == 1.0
    assert flops.roofline_seconds(0.0, 819e9, pk) == 1.0
