"""The reduction from a trace to the per-layer numbers, on small traces
whose answers are known by hand."""
import pytest

from bench import trace
from bench.trace import Op, Trace


GEMM = ('%jvp_panel_gemm_.2 = f32[2,128,128]{2,1,0} custom-call('
        'f32[2,128,128]{2,1,0} %p, f32[2,128,128]{2,1,0} %q), '
        'custom_call_target="tpu_custom_call"')


def _trace():
    tr = Trace(window=(0.0, 10.0))
    tr.ops[0] = [Op(1.0, 3.0, "fusion.1"), Op(2.0, 4.0, "jvp_panel_gemm_.2",
                                                GEMM),
                 Op(4.0, 4.0, "panel_gemm_bias_relu.3", GEMM),
                 Op(6.0, 7.0, "all-gather.3"), Op(6.5, 6.75, "fusion.4"),
                 Op(9.5, 11.0, "fusion.1")]
    tr.ops[1] = [Op(0.0, 5.0, "fusion.1")]
    tr.modules[0] = [Op(1.0, 4.0, "jit__decode_fn(1)"),
                     Op(6.0, 7.0, "jit_other(2)")]
    tr.spans = [Op(0.0, 10.0, "bench:window"), Op(4.0, 6.0, "bench:step"),
                Op(4.5, 5.5, "PjitFunction(_decode_fn)"),
                Op(7.0, 9.5, "bench:idle")]
    return tr


def test_merge_and_measure():
    assert trace.merge([(3, 4), (1, 2), (1.5, 2.5), (5, 5)]) == \
        [(1, 2.5), (3, 4)]
    assert trace.measure([(0, 1), (0.5, 2), (3, 4)]) == 3.0


def test_subtract():
    assert trace.subtract([(0, 10)], [(1, 2), (5, 7)]) == 7.0
    assert trace.subtract([(0, 1), (2, 3)], [(0.5, 2.5)]) == 1.0
    assert trace.subtract([(0, 1)], []) == 1.0


def test_busy_is_union_clipped_and_averaged_over_chips():
    tr = _trace()
    # chip 0: [1,4] + [6,7] + [9.5,10] = 4.5; chip 1: [0,5] = 5
    assert trace.busy_seconds(tr) == pytest.approx((4.5 + 5.0) / 2)
    assert tr.window_s == 10.0


def test_kernel_time_by_name():
    tr = _trace()
    ops = trace.kernel_ops(tr, "panel_gemm")
    assert [(o.start, o.end) for o in ops] == [(2.0, 4.0)]
    assert trace.kernel_ops(tr, "panel") == []     # whole names only
    assert [o.name for o in trace.kernel_ops(tr, "panel_gemm_bias_relu")] \
        == ["panel_gemm_bias_relu.3"]
    assert trace.short_name("%fusion.3 = f32[2]{0} fusion(%a)") == "fusion.3"
    assert [o.name for o in trace.module_ops(tr, "_decode_fn")] == \
        ["jit__decode_fn(1)"]


def test_exposed_collective():
    tr = _trace()
    tot, exp = trace.exposed_collective_seconds(tr)
    # chip 0: all-gather [6,7] with compute over [6.5,6.75]; chip 1: none
    assert tot == pytest.approx(1.0 / 2)
    assert exp == pytest.approx(0.75 / 2)


def test_idle_gaps_are_labelled_by_host_span():
    tr = _trace()
    gaps = dict((k, v) for k, v in trace.idle_gaps(tr))
    # chip 0 idle: [0,1] outside spans, [4,6] in bench:step (its middle
    # at 5 is inside the jitted call), [7,9.5] in bench:idle
    assert gaps == pytest.approx({"outside bench spans": 1.0,
                                  "step/PjitFunction(_decode_fn)": 2.0,
                                  "idle": 2.5})
    top = dict(trace.top_ops(tr))
    assert top["fusion.1"] == pytest.approx((2.0 + 0.5 + 5.0) / 2)
    assert top["jvp_panel_gemm_.2"] == pytest.approx(1.0)


def test_load_reads_a_recorded_trace(tmp_path):
    """A real profile written by ``jax.profiler`` on this host: the
    window span and the host spans are found; a host has no TPU planes."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("bench:step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    assert tr.window is not None and tr.window_s > 0
    assert any(s.name == "bench:step" for s in tr.spans)
    assert tr.ops == {}
    assert trace.busy_seconds(tr) == 0.0


def test_kernel_roofline_readers():
    """The readers take each call at its logical size and find the fused
    Adam's calls under a vmap by their signature."""
    import json
    from pathlib import Path

    from bench import flops
    from bench.run import load_reader
    cfg = json.loads((Path(__file__).resolve().parents[2] / "bench"
                      / "configs" / "lenet-cifar.json").read_text())
    pk = flops.peaks("TPU v5 lite")
    gemm = ("%vmap_jvp_panel_gemm__.6 = f32[16,1,32768,128]{3,2,1,0} "
            "custom-call(f32[16,1,32768,128]{3,2,1,0} %a, "
            "f32[16,1,128,128]{3,2,1,0} %b), "
            'custom_call_target="tpu_custom_call"')
    adam = ("%closed_call.9 = (f32[1,120]{1,0}, f32[1,120]{1,0}, "
            "f32[1,120]{1,0}) custom-call(f32[2]{0} %s, f32[1,120]{1,0} %p,"
            " f32[1,120]{1,0} %g, f32[1,120]{1,0} %m, f32[1,120]{1,0} %v), "
            'custom_call_target="tpu_custom_call"')
    tr = Trace(window=(0.0, 1.0))
    tr.ops[0] = [Op(0.0, 0.5, "vmap_jvp_panel_gemm__.6", gemm),
                 Op(0.5, 0.5 + 1e-5, "closed_call.9", adam)]
    run = {"trace": tr, "config": cfg, "peaks": pk}
    least = 4 * (16 * 32768 * 75 + 16 * 75 * 6 + 16 * 32768 * 6) / 819e9
    assert load_reader("panel_gemm_roofline")(run) == \
        pytest.approx(100 * least / 0.5)
    assert load_reader("masked_adam_roofline")(run) == \
        pytest.approx(100 * (4 * (7 * 120 + 2)) / 819e9 / 1e-5)
