"""Roofline share of the batched decode step (the jitted ``_decode_fn``
of ``serve/continuous.py``): per step, the served weights read once and
the valid cached keys and values of its active slots at the chip's HBM
bandwidth (``bench.flops.lm_decode_step_bytes``), over the device time of
the step's program, in %."""
from bench import flops, trace


def read(run):
    steps = trace.module_ops(run["trace"], "_decode_fn")
    kv = run["counts"].get("decode_kv_tokens") or []
    # the trace may close on a step still running: pair them in order
    if not steps or abs(len(steps) - len(kv)) > 2:
        return None
    n = min(len(steps), len(kv))
    steps, kv = steps[:n], kv[:n]
    least = sum(flops.lm_decode_step_bytes(run["config"], n) for n in kv) \
        / run["peaks"]["hbm_bytes_per_s"]
    took = sum(o.end - o.start for o in steps)
    return 100.0 * least / took if took > 0 else None
