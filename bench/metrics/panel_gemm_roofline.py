"""Roofline share of the client-conv Pallas GEMM (``panel_gemm`` in
``kernels/client_conv.py``): the least time its calls in the window
could take, each at the logical size of the conv block its trace event's
operand shapes name (``bench.flops.panel_gemm_cost``: the padding to 128
lanes counts as waste), over the time they took, in %."""
from bench import flops, trace


def read(run):
    ops = trace.kernel_ops(run["trace"], "panel_gemm")
    if not ops:
        return None
    panels = flops.lenet_conv_panels(run["config"])
    least = 0.0
    for o in ops:
        cost = flops.panel_gemm_cost(o.text, panels)
        if cost is None:
            return None
        least += flops.roofline_seconds(*cost, run["peaks"])
    took = sum(o.end - o.start for o in ops)
    return 100.0 * least / took if took > 0 else None
