"""Roofline share of the fused Adam Pallas kernel (``masked_adam`` in
``kernels/masked_adam.py``; the server and per-client mask updates): the
least time its calls could take, from the bytes of the arrays each event
names (it is bound by memory), over the time they took, in %.

Under the per-client ``vmap`` XLA names the kernel's calls
``closed_call.<n>``; those are found by the kernel's signature: three
results of one shape from the two bias-correction scalars and four
operands of that shape (p, g, mu, nu; a fifth when a mask is passed).
"""
from bench import flops, trace


def _adam_call(op) -> bool:
    if not op.name.startswith("closed_call"):
        return False
    shapes = flops.hlo_shapes(op.text)
    res, args = shapes[:3], shapes[3:]
    return (len(res) == 3 and len(args) in (5, 6)
            and res[0] == res[1] == res[2] and args[0][1] == (2,)
            and all(a[1] == res[0][1] for a in args[1:]))


def read(run):
    tr = run["trace"]
    ops = trace.kernel_ops(tr, "masked_adam") + [
        o for chip in tr.ops.values() for o in tr.clip(chip)
        if o.text and _adam_call(o)]
    if not ops:
        return None
    least = 0.0
    for o in ops:
        cost = flops.elementwise_cost(o.text, flops_per_element=12.0)
        if cost is None:
            return None
        least += flops.roofline_seconds(*cost, run["peaks"])
    took = sum(o.end - o.start for o in ops)
    return 100.0 * least / took if took > 0 else None
