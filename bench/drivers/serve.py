"""Personalised serving: ``ContinuousEngine.submit`` / ``step`` in an open
loop.

Set-up makes the served weights and every client's unit masks on the
device from the seed, builds one engine, and warms exactly the shapes the
window's traffic uses (one request per prompt bucket, and the
completion slice of every answer budget).  The window submits each
request at its due time whether or not earlier ones have finished, and
steps the engine in between.  Every request is timed from its due time.
When the window closes, the requests still in flight are finished (their
latency counts the wait); then a sample of the finished requests is
checked against the plain reference.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

from bench import flops, traffic
from bench.harness import Ctx, check, free_device_memory, log, \
    memory_peak_bytes, percentile
from bench.reference import qwen2 as ref

DRAIN_S = 60.0          # how long finished-late requests are waited for


def program_config(cfgj: dict):
    from repro.configs.base import get_config
    return dataclasses.replace(
        get_config(cfgj["model"]), n_layers=cfgj["num_hidden_layers"],
        d_model=cfgj["hidden_size"], n_heads=cfgj["num_attention_heads"],
        n_kv_heads=cfgj["num_key_value_heads"], head_dim=cfgj["head_dim"],
        d_ff=cfgj["intermediate_size"], vocab_size=cfgj["vocab_size"],
        rope_theta=cfgj["rope_theta"], qkv_bias=cfgj["qkv_bias"],
        mu=cfgj["mu"], dtype=cfgj["dtype"])


def _check_layout(cfg, cfgj, params, masks, population):
    """The benchmark's weights and masks must have the program's layout,
    shapes and types."""
    import jax
    from repro.core import masks as masks_mod
    from repro.launch.steps import init_serve_params
    want = jax.eval_shape(lambda: init_serve_params(
        cfg, jax.random.PRNGKey(0), dtype=cfgj["dtype"]))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("served weights: the program's layout "
                         f"{want} is not the benchmark's {got}")
    want_m = jax.eval_shape(lambda: masks_mod.init_unit_masks(cfg,
                                                              population))
    got_m = jax.eval_shape(lambda: masks)
    if jax.tree.structure(want_m) != jax.tree.structure(got_m) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want_m),
                                               jax.tree.leaves(got_m))):
        raise ValueError(f"masks: {want_m} vs {got_m}")


def _all_masks(cfgj, key, population, keep):
    rows = ref.mask_rows(cfgj, key, np.arange(population), keep)
    return [{"0": {"mixer": rows["mixer"], "ffn": rows["ffn"]}}]


def _bucket(n: int, cap: int) -> int:
    """The engine's prompt bucket: the next power of two from 8."""
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


def build(ctx: Ctx):
    import jax
    from repro.serve import ContinuousEngine
    cfgj, w = ctx.config, ctx.workload
    cfg = program_config(cfgj)
    params = jax.jit(functools.partial(
        ref.init_params, cfgj, served_dtype=cfgj["dtype"]))(
        jax.random.PRNGKey(ctx.sub_seed(3)))
    masks = jax.jit(functools.partial(
        _all_masks, cfgj, population=w["clients"]["population"],
        keep=w["mask_keep"]))(jax.random.PRNGKey(ctx.sub_seed(4)))
    _check_layout(cfg, cfgj, params, masks, w["clients"]["population"])
    eng = ContinuousEngine(cfg, params, masks, max_batch=w["max_batch"],
                           cache_len=w["cache_len"])
    jax.block_until_ready((params, masks))
    return eng


def warm(eng, ctx: Ctx, reqs):
    """Prefill once at every prompt bucket the window's requests fall in,
    decode and admit, and read back every answer length they ask for."""
    import jax.numpy as jnp
    from repro.serve import Request
    w = ctx.workload
    cap = w["cache_len"]
    warm_reqs = []
    for i, b in enumerate(sorted({_bucket(len(r.prompt), cap)
                                  for r in reqs})):
        L = min(b, cap - 2)
        warm_reqs.append(Request(-1 - i, reqs[i % len(reqs)].client,
                                 np.zeros(L, np.int32), 2))
    for r in warm_reqs:
        eng.submit(r)
    eng.run_until_idle()
    probe = jnp.zeros((w["max_batch"], cap), jnp.int32)
    for n in sorted({r.budget for r in reqs}):
        np.asarray(probe[0, :n])


def open_loop(eng, ctx: Ctx, trace, window: float):
    """Submit each request at its due time, step the engine in between.
    Returns (the requests submitted, wall time the window opened, how
    late each submission was in seconds)."""
    from repro.serve import Request
    reqs = [Request(t.index, t.client, t.prompt, t.budget) for t in trace]
    late = []
    i, n = 0, len(trace)
    with ctx.window() as t0:
        wall0 = time.time() - (time.perf_counter() - t0)
        while True:
            now = time.perf_counter() - t0
            while i < n and trace[i].due_s <= now:
                reqs[i].t_submit = wall0 + trace[i].due_s
                with ctx.span("submit"):
                    eng.submit(reqs[i])
                late.append(now - trace[i].due_s)
                i += 1
            if now >= window:             # every request is due by now
                break
            with ctx.span("step"):
                busy = eng.step()
            if not busy and i < n:
                pause = trace[i].due_s - (time.perf_counter() - t0)
                if pause > 0:
                    with ctx.span("idle"):
                        time.sleep(min(pause, window - now))
    return reqs[:i], wall0, late


def _sample(ctx: Ctx, done, target_tokens: int):
    """Finished requests to check: the longest, then others in an order
    drawn from the seed, until ``target_tokens`` served tokens."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + r.max_new_tokens)
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(ctx.sub_seed(5)).permutation(len(rest))
    out, total = [longest], longest.max_new_tokens
    for j in order:
        if total >= target_tokens:
            break
        out.append(rest[j])
        total += rest[j].max_new_tokens
    return out


def gaps(ctx: Ctx, sample, *, outputs=None, quant=None):
    """Per checked request: the widest gap, in units of that position's
    logit standard deviation, by which the served token's reference logit
    lies below the reference's best.  With ``quant``, the token read is
    the one a float8 copy of the reference puts first (the control).
    ``outputs`` replaces the served tokens (a planted fault)."""
    import jax
    import jax.numpy as jnp
    cfgj, w = ctx.config, ctx.workload
    params = jax.jit(functools.partial(
        ref.init_params, cfgj, served_dtype=cfgj["dtype"]))(
        jax.random.PRNGKey(ctx.sub_seed(3)))
    S = w["cache_len"]
    fn = jax.jit(functools.partial(ref.logits_at, cfgj))
    fq = jax.jit(functools.partial(ref.logits_at, cfgj, quant=quant)) \
        if quant else None
    worst = []
    rows = ref.mask_rows(cfgj, jax.random.PRNGKey(ctx.sub_seed(4)),
                         [r.client_id for r in sample], w["mask_keep"])
    for j, r in enumerate(sample):
        out = np.asarray(outputs[j] if outputs is not None else r.output)
        L, n = len(r.prompt), len(out)
        seq = np.zeros(S, np.int32)
        seq[:L] = r.prompt
        seq[L:L + n - 1] = out[:n - 1]
        where = np.full(w["budget"]["max"], L - 1, np.int32)
        where[:n] = np.arange(L - 1, L - 1 + n)
        g = jax.tree.map(lambda a: a[j], rows)
        lg = np.asarray(fn(params, jnp.asarray(seq), jnp.asarray(where), g),
                        np.float64)[:n]
        tok = out if fq is None else np.argmax(np.asarray(
            fq(params, jnp.asarray(seq), jnp.asarray(where), g))[:n], -1)
        best = lg.max(-1)
        worst.append(float(np.max((best - lg[np.arange(n), tok])
                                  / lg.std(-1))))
    return worst


def _alter(ctx: Ctx, r):
    """The served tokens with one of them, at a place drawn from the seed,
    replaced by the next token id: a token altered where it is made."""
    out = np.array(r.output, np.int32)
    j = int(np.random.default_rng([ctx.seed, r.req_id]).integers(len(out)))
    out[j] = (out[j] + 1) % ctx.config["vocab_size"]
    return out


def run(ctx: Ctx, readings=None) -> dict:
    """One run of the cell.  ``readings``: a dict to fill with what the
    control and a planted fault read on this run's sample (for
    ``bench.control``; the benchmark's own runs leave it None)."""
    cfgj, w = ctx.config, ctx.workload
    window = ctx.window_seconds()
    trace = traffic.serve_trace(w, ctx.seed, window, cfgj["vocab_size"])
    t0 = time.perf_counter()
    eng = build(ctx)
    t1 = time.perf_counter()
    warm(eng, ctx, trace)
    setup_s = ctx.setup_done()
    log(f"[serve] {len(trace)} requests over {window}s "
        f"({w['rate_per_s']}/s); set-up {setup_s:.3f}s: weights, masks "
        f"and engine {t1 - t0:.3f}s, warm-up {time.perf_counter() - t1:.3f}s;"
        f" memory peak after set-up "
        f"{memory_peak_bytes(ctx.devices[:ctx.chips])} B")

    decode_kv = []
    if ctx.trace:                  # each decode step's slot positions
        inner = eng._decode

        def counted(params, cache, tok, pos, *a):
            decode_kv.append(pos)
            return inner(params, cache, tok, pos, *a)
        eng._decode = counted
    reqs, wall0, late = open_loop(eng, ctx, trace, window)
    if ctx.trace:
        eng._decode = inner              # the engine's own again
    w_end = wall0 + ctx.window_s
    t_drain = time.perf_counter()
    while not eng.sched.idle() and time.perf_counter() - t_drain < DRAIN_S:
        eng.step()
    mem = memory_peak_bytes(ctx.devices[:ctx.chips])

    done = [r for r in reqs if r.output is not None]
    lat = [(r.t_done - r.t_submit) * 1e3 if r.output is not None
           else float("inf") for r in reqs]
    lat += [float("inf")] * (len(trace) - len(reqs))
    waits = [(r.t_admit - r.t_submit) * 1e3 if r.t_admit else float("inf")
             for r in reqs] + [float("inf")] * (len(trace) - len(reqs))
    in_window = [r for r in done if r.t_done <= w_end]
    log(f"[serve] submitted {len(reqs)}/{len(trace)}, finished "
        f"{len(done)} ({len(in_window)} inside the window), memory peak "
        f"{mem} B, generator "
        f"late by at most {max(late, default=0.0) * 1e3:.3f}ms; "
        f"gate cache hits {eng.stats.gate_hits} misses "
        f"{eng.stats.gate_misses}; decode steps {eng.stats.decode_steps}")
    pos = [np.asarray(p) for p in decode_kv]     # free slots sit at 0
    counts = {
        "decode_active": [int(np.sum(p > 0)) for p in pos],
        "decode_kv_tokens": [int(np.sum(np.where(p > 0, p + 1, 0)))
                             for p in pos],
        "prompt_tokens": sum(len(r.prompt) for r in reqs if r.t_admit
                             and r.t_admit <= w_end),
        "prompt_flops": sum(flops.lm_prefill_flops(cfgj, len(r.prompt))
                            for r in reqs if r.t_admit
                            and r.t_admit <= w_end),
        "queue_wait_ms": waits,
    }
    sample = _sample(ctx, done, w["check_tokens"])
    del eng
    free_device_memory()

    t_ref = time.perf_counter()
    worst = gaps(ctx, sample)
    log(f"[serve] checked {len(sample)} requests, "
        f"{sum(r.max_new_tokens for r in sample)} served tokens, against "
        f"the reference in {time.perf_counter() - t_ref:.3f}s; widest gap "
        f"per request {[round(x, 4) for x in worst]}")
    if readings is not None:
        readings["program"] = worst
        readings["control_fp8"] = gaps(ctx, sample, quant="fp8")
        readings["altered_token"] = gaps(ctx, sample, outputs=[
            _alter(ctx, r) for r in sample])
    checks = [check("served_gap", max(worst) if worst else float("inf"),
                    w["limits"]["served_gap"]),
              check("unfinished", len(trace) - len(done), 0)]
    return {
        "e2e": {"serve_latency_p95_ms": percentile(lat, 95),
                "setup_s": setup_s},
        "attempted": len(trace), "failed": len(trace) - len(done),
        "checks": checks, "counts": counts, "memory_peak_bytes": mem,
    }
