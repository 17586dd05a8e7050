"""Smoke run of AdaSplit's two jobs on TPU, through their normal entry points.

    python chip_smoke.py              # one chip: training, then serving
    python chip_smoke.py --chips 4    # cohort-sharded training on four chips
                                      # against the same run on one chip

Training runs the paper protocol (LeNet at its published widths, 5
Mixed-NonIID clients, batch 32, kappa 0.6) through ``AdaSplitTrainer``,
once as ``python -m repro.launch.train --arch lenet-cifar`` configures it
(``epoch_scan``) and once on the default ``round_scan`` rung.  Each run is
checked against the plain reference (eager driver, ``lax`` convs, plain
Adam) on the same seed and data: selections exact, per-round server CE
within ``CE_TOL``, meter bytes equal, CE falling, and the compiled round
program must hold the native Pallas kernels (``tpu_custom_call``).

Serving answers 16 personalised requests with ``ContinuousEngine`` on
qwen2-0.5b at its published widths and compares them with the FIFO
``ServeEngine``: in float32 at full matmul precision, first tokens equal
and greedy token agreement >= 90%; in bfloat16 as served, every point
where the engines' greedy paths part must be a near-tie of the float32
reference.  The native kernels are also checked against plain XLA.

Every input is made from a seed.  The script prints what it finds, then
one JSON line.  It exits non-zero, without that line, when JAX sees no
TPU, when the ``repro`` package is not beside it, or when any check fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
import warnings
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

TRAIN = dict(n_clients=5, n_per_client=1000, n_test=200, rounds=6,
             kappa=0.6, batch=32, seed=0)
# Per-round mean server CE, fast path against the plain reference.  On
# the TPU both paths multiply f32 operands in one bf16 pass (the Pallas
# GEMM is off by ~2.4e-3 of its output scale, see GEMM_TOL), each with
# its own rounding, and Adam carries the difference through the local
# phase's 124 client steps: on a v5e the rounds differed by 9.5e-5 and
# 1.8e-2 nats.  0.05 nats is half the CE's fall over those two rounds
# (~0.11) and far above f32 noise.
CE_TOL = 0.05
# Selections must match the reference exactly through the first global
# round; after it the precision drift above may reorder a near-tie in
# the UCB advantages (on a v5e the first difference came 2 iterations
# into the second round; the count of equal iterations is printed).
EXACT_ROUNDS = 1
# Native kernels against plain XLA on one input.  The GEMM's error is
# relative to the output's largest magnitude: a one-pass bf16 MXU
# product is off by up to 2^-8 relative per term (measured 2.4e-3 on a
# v5e), so 1e-2 admits it and catches a wrong tile.  Adam is elementwise
# f32 in both.
GEMM_TOL = 1e-2
ADAM_TOL = 1e-5
SERVE = dict(n_clients=4, n_requests=16, prompt=(32, 256), budget=(16, 64),
             max_batch=8, seed=0)
AGREEMENT_MIN = 0.9          # the bar benchmarks/serve_traffic.py uses
# A bfloat16 divergence is a near-tie when the float32 reference puts the
# two engines' tokens within 0.1 standard deviations of that position's
# logits.  bfloat16 keeps 8 significant bits, so 24 layers of it move
# logits by a few hundredths of a deviation; a wrong token from a fault
# sits O(1) deviations away (two random tokens differ by ~1.4).
TIE_TOL = 0.1
KERNELS = {"panel_gemm", "masked_adam"}   # Pallas names on the train path


class CheckFailed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise CheckFailed(msg)


def log(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def _record_iterations(tr):
    """Every driver (eager ``update``, round and epoch ingestion) appends
    one (selection mask, per-client CE) pair per global iteration to the
    orchestrator's history: record them in order."""
    its = []
    append = tr.orch._append_history

    def spy(mask, dense):
        its.append((np.flatnonzero(np.asarray(mask) > 0),
                    np.asarray(dense, np.float64)))
        append(mask, dense)

    tr.orch._append_history = spy
    return its


def run_trainer(cfg, clients, *, mesh=None, **hp_kw):
    from repro.core.adasplit import AdaSplitHParams, AdaSplitTrainer
    hp = AdaSplitHParams(rounds=TRAIN["rounds"], kappa=TRAIN["kappa"],
                         batch_size=TRAIN["batch"], seed=TRAIN["seed"],
                         **hp_kw)
    t0 = time.perf_counter()
    tr = AdaSplitTrainer(cfg, hp, clients, mesh=mesh)
    its = _record_iterations(tr)
    hist = tr.train(eval_every=max(hp.rounds // 2, 1))
    return {"tr": tr, "its": its, "hist": hist,
            "seconds": time.perf_counter() - t0}


def round_ces(run, T):
    """Per global round: mean CE over its iterations' selected clients."""
    its = run["its"]
    check(len(its) % T == 0, f"{len(its)} global iterations, T={T}")
    return [float(np.mean([d[s].mean() for s, d in its[r:r + T]]))
            for r in range(0, len(its), T)]


def compare_runs(name, run, ref, T, *, ce_tol, exact_rounds):
    """Selections exact over the first ``exact_rounds`` global rounds,
    per-round CE within ``ce_tol``, meter bytes and FLOPs equal.  Logs
    everything before checking."""
    sel = [tuple(s) for s, _ in run["its"]]
    sel_ref = [tuple(s) for s, _ in ref["its"]]
    diff = [i for i, (a, b) in enumerate(zip(sel, sel_ref)) if a != b]
    ces, ces_ref = round_ces(run, T), round_ces(ref, T)
    deltas = [abs(a - b) for a, b in zip(ces, ces_ref)]
    log(f"  {name}: accuracy {run['hist'][-1]['accuracy']:.2f}% vs "
        f"reference {ref['hist'][-1]['accuracy']:.2f}%; selections equal "
        f"in {len(sel) - len(diff)}/{len(sel)} global iterations (first "
        f"difference: {diff[0] if diff else None}); per-round CE "
        f"{[round(c, 5) for c in ces]} vs {[round(c, 5) for c in ces_ref]}"
        f", |delta| {deltas}")
    check(len(sel) == len(sel_ref) > 0,
          f"{name}: {len(sel)} global iterations vs reference "
          f"{len(sel_ref)}")
    check(not diff or diff[0] >= exact_rounds * T,
          f"{name}: selections differ from the reference at global "
          f"iteration {diff[0] if diff else None}, inside the first "
          f"{exact_rounds} global round(s)")
    check(max(deltas) <= ce_tol,
          f"{name}: CE differs from the reference by {max(deltas)} > "
          f"{ce_tol}")
    m, mr = run["tr"].meter, ref["tr"].meter
    for field in ("bandwidth_bytes", "host_device_bytes", "client_flops",
                  "server_flops"):
        check(getattr(m, field) == getattr(mr, field),
              f"{name}: meter {field} {getattr(m, field)} != reference "
              f"{getattr(mr, field)}")


def compiled_round_kernels(tr, T, *, epoch_rounds=0):
    """Compile the trainer's global-phase round (or epoch) program from
    shapes alone and list the Pallas kernels in it."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import compiled_kernels
    hp, cfg = tr.hp, tr.cfg
    lead = (epoch_rounds, T) if epoch_rounds else (T,)
    fn = (tr._epoch_fn(epoch_rounds, T, True) if epoch_rounds
          else tr._round_fn(T, True))
    s = cfg.image_size
    args = (jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         tr._carry()),
            jax.ShapeDtypeStruct(lead + (tr.n, hp.batch_size, s, s, 3),
                                 jnp.float32),
            jax.ShapeDtypeStruct(lead + (tr.n, hp.batch_size), jnp.int32),
            jax.ShapeDtypeStruct(lead, jnp.int32))
    return compiled_kernels(fn.lower(*args).compile().as_text())


def train_phase():
    from repro.configs.base import get_config
    from repro.data.synthetic import mixed_noniid
    from repro.kernels.client_conv import default_method
    check(default_method() == "pallas",
          f"client conv resolves to {default_method()!r}")
    cfg = get_config("lenet-cifar")
    clients = mixed_noniid(n_clients=TRAIN["n_clients"],
                           n_per_client=TRAIN["n_per_client"],
                           n_test=TRAIN["n_test"], seed=TRAIN["seed"])
    T = TRAIN["n_per_client"] // TRAIN["batch"]
    log(f"[train] lenet-cifar, {TRAIN['n_clients']} mixed-noniid clients, "
        f"batch {TRAIN['batch']}, {TRAIN['rounds']} rounds, "
        f"kappa {TRAIN['kappa']}, T={T}")
    ref = run_trainer(cfg, clients, round_scan=False, batched_conv=False,
                      fused_mask_adam=False, fused_server_adam=False)
    log(f"  reference (eager, lax conv, plain Adam): {ref['seconds']:.1f}s")
    problems = []
    for name, kw in (("epoch_scan", {"epoch_scan": True}),
                     ("round_scan", {})):
        run = run_trainer(cfg, clients, **kw)
        log(f"  {name}: {run['seconds']:.1f}s")
        try:
            compare_runs(name, run, ref, T, ce_tol=CE_TOL,
                         exact_rounds=EXACT_ROUNDS)
            ces = round_ces(run, T)
            check(ces[-1] < ces[0],
                  f"{name}: server CE did not fall ({ces[0]} -> {ces[-1]})")
            # the epoch rung runs the global rounds as one epoch
            n_global = TRAIN["rounds"] - round(TRAIN["kappa"]
                                               * TRAIN["rounds"])
            got = compiled_round_kernels(run["tr"], T, epoch_rounds=(
                n_global if kw else 0)) & KERNELS
            log(f"  {name}: Pallas kernels in the compiled global program: "
                f"{sorted(got)}")
            check(got == KERNELS, f"{name}: native kernels missing")
        except CheckFailed as e:
            log(f"  FAILED {e}")
            problems.append(str(e))
    check(not problems, "; ".join(problems))


def kernel_phase():
    """The native kernels against plain XLA on the same inputs: the
    client-conv GEMM at lenet's first block (C=5, B=32) and fused Adam
    on the server leaves and on vmapped per-client mask rows."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.core.masks import init_lenet_unit_masks
    from repro.kernels.client_conv import panel_gemm
    from repro.kernels.masked_adam import fused_adam_update
    from repro.models import lenet
    from repro.optim.adam import adam_init, adam_update
    rng = np.random.default_rng(TRAIN["seed"])
    C, M, K, N = TRAIN["n_clients"], TRAIN["batch"] * 32 * 32, 75, 6
    a = jnp.asarray(rng.normal(size=(C, M, K)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(C, K, N)), jnp.float32)
    want = jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    got = jax.jit(panel_gemm)(a, b)
    gemm_err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    cfg = get_config("lenet-cifar")
    sp = lenet.init_server_params(cfg, jax.random.PRNGKey(TRAIN["seed"]))
    masks = init_lenet_unit_masks(cfg, 3)

    def noisy(tree, scale):
        return jax.tree.map(lambda p: jnp.asarray(
            rng.normal(size=p.shape) * scale, jnp.float32), tree)

    def rel_err(x, y):
        return max(float(jnp.max(jnp.abs(u - v)) /
                         (jnp.max(jnp.abs(v)) + 1e-30))
                   for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y)))

    g, opt = noisy(sp, 1.0), adam_init(sp)
    opt["mu"], opt["nu"] = noisy(sp, 0.1), jax.tree.map(jnp.abs,
                                                         noisy(sp, 0.1))
    fused = jax.jit(lambda p, g, o: fused_adam_update(p, g, o, lr=1e-3))
    plain = jax.jit(lambda p, g, o: adam_update(p, g, o, lr=1e-3))
    adam_err = rel_err(fused(sp, g, opt), plain(sp, g, opt))
    mo = adam_init(masks)
    mo["step"] = jnp.asarray([0, 4, 9], jnp.int32)   # per-client steps
    gm = noisy(masks, 1.0)
    mask_err = rel_err(jax.vmap(fused)(masks, gm, mo),
                       jax.vmap(plain)(masks, gm, mo))
    log(f"[kernels] panel_gemm vs f32 XLA matmul: max rel err {gemm_err:.3e}"
        f"; fused Adam vs adam_update: server {adam_err:.3e}, vmapped "
        f"masks {mask_err:.3e}")
    check(gemm_err <= GEMM_TOL, f"panel_gemm error {gemm_err} > {GEMM_TOL}")
    check(max(adam_err, mask_err) <= ADAM_TOL,
          f"fused Adam error {max(adam_err, mask_err)} > {ADAM_TOL}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serve_requests(cfg):
    from repro.serve import Request
    rng = np.random.default_rng(SERVE["seed"])
    lo, hi = SERVE["prompt"]
    blo, bhi = SERVE["budget"]
    reqs = []
    for i in range(SERVE["n_requests"]):
        client = rng.integers(SERVE["n_clients"])
        prompt = rng.integers(0, cfg.vocab_size, rng.integers(lo, hi + 1),
                              dtype=np.int32)
        reqs.append(Request(i, int(client), prompt,
                            int(rng.integers(blo, bhi + 1))))
    return reqs


def _serve_both(cfg, params, masks):
    """The same requests through ContinuousEngine and the FIFO
    ServeEngine oracle; returns (continuous, oracle) request lists."""
    from repro.serve import ContinuousEngine, ServeEngine
    t0 = time.perf_counter()
    cont = ContinuousEngine(cfg, params, masks, max_batch=SERVE["max_batch"],
                            cache_len=SERVE["prompt"][1] + SERVE["budget"][1])
    reqs = serve_requests(cfg)
    for r in reqs:
        cont.submit(r)
    done = cont.run_until_idle()
    check(len(done) == len(reqs), f"continuous engine finished {len(done)} "
          f"of {len(reqs)} requests")
    t1 = time.perf_counter()
    fifo = ServeEngine(cfg, params, masks, max_batch=SERVE["max_batch"],
                       mixed_batches=True)
    oracle = serve_requests(cfg)
    for r in oracle:
        fifo.submit(r)
    fifo.run_until_idle()
    log(f"  {cfg.dtype}: continuous {t1 - t0:.1f}s, fifo oracle "
        f"{time.perf_counter() - t1:.1f}s (compiles included)")
    for a, b in zip(reqs, oracle):
        check(len(a.output) == len(b.output) == a.max_new_tokens,
              f"request {a.req_id}: {len(a.output)} tokens vs "
              f"{len(b.output)}, budget {a.max_new_tokens}")
    return reqs, oracle


def _agreement(reqs, oracle):
    """(first tokens equal, token agreement, first differing position per
    diverging request)."""
    first = sum(int(a.output[0]) == int(b.output[0])
                for a, b in zip(reqs, oracle))
    same = sum(int((np.asarray(a.output) == np.asarray(b.output)).sum())
               for a, b in zip(reqs, oracle))
    total = sum(r.max_new_tokens for r in reqs)
    div = {}
    for a, b in zip(reqs, oracle):
        d = np.flatnonzero(np.asarray(a.output) != np.asarray(b.output))
        if d.size:
            div[a.req_id] = int(d[0])
    return first, same / total, div


def _tie_margins(cfg32, params32, masks, reqs, oracle, div):
    """At each divergence, the float32 reference's logit margin between
    the two engines' tokens, in units of that position's logit standard
    deviation: a single-example prefill of the prompt plus the common
    prefix, at full matmul precision."""
    import jax
    import jax.numpy as jnp
    from repro.core import masks as masks_mod
    from repro.models import decode as dec
    margins = {}
    with jax.default_matmul_precision("highest"):
        for a, b in zip(reqs, oracle):
            if a.req_id not in div:
                continue
            p = div[a.req_id]
            seq = np.concatenate([a.prompt, a.output[:p]]).astype(np.int32)
            lg, _ = dec.prefill(
                cfg32, params32, jnp.asarray(seq[None]),
                gates=masks_mod.gates_for_client(masks, a.client_id))
            lg = np.asarray(lg, np.float64).reshape(-1)[:cfg32.vocab_size]
            margins[a.req_id] = float(
                abs(lg[a.output[p]] - lg[b.output[p]]) / lg.std())
    return margins


def serve_phase():
    """Checked first in float32 at full matmul precision, where the two
    engines differ only in the order of their sums: first tokens equal
    and token agreement >= 90%.  Then in bfloat16 at the TPU's default
    precision, as served, where one flipped rounding can swap a
    near-tied argmax and the rest of that request follows another greedy
    path: every divergence there must sit at a near-tie of the float32
    reference (``TIE_TOL``)."""
    import contextlib
    import dataclasses

    import jax
    from repro.configs.base import get_config
    from repro.core import masks as masks_mod
    from repro.launch.steps import init_serve_params
    base = get_config("qwen2-0.5b")
    log(f"[serve] {base.name}: {base.n_layers} layers, d_model "
        f"{base.d_model}, vocab {base.vocab_size}, {SERVE['n_clients']} "
        f"clients, {SERVE['n_requests']} requests, max_batch "
        f"{SERVE['max_batch']}")
    key = jax.random.PRNGKey(9)
    masks = jax.tree.map(
        lambda m: (jax.random.uniform(jax.random.fold_in(key, m.size),
                                      m.shape) > 0.4).astype(m.dtype),
        masks_mod.init_unit_masks(base, SERVE["n_clients"]))
    out = {}
    cfg32 = dataclasses.replace(base, dtype="float32")
    params32 = init_serve_params(cfg32, jax.random.PRNGKey(SERVE["seed"]),
                                 dtype="float32")
    for cfg in (cfg32, base):
        params = params32 if cfg is cfg32 else init_serve_params(
            cfg, jax.random.PRNGKey(SERVE["seed"]), dtype=cfg.dtype)
        with (jax.default_matmul_precision("highest") if cfg is cfg32
              else contextlib.nullcontext()):
            reqs, oracle = _serve_both(cfg, params, masks)
        first, agreement, div = _agreement(reqs, oracle)
        log(f"  {cfg.dtype}: first tokens equal {first}/{len(reqs)}; greedy "
            f"token agreement {agreement:.4f}; first divergence per "
            f"request {div}")
        out[cfg.dtype] = {"reqs": reqs, "oracle": oracle, "first": first,
                          "agreement": agreement, "div": div}
    bf16, f32 = out["bfloat16"], out["float32"]
    margins = _tie_margins(cfg32, params32, masks, bf16["reqs"],
                           bf16["oracle"], bf16["div"])
    log(f"  bfloat16 divergences: float32 reference margin / logit std "
        f"{margins}")
    check(f32["first"] == SERVE["n_requests"], "float32: first tokens differ")
    check(f32["agreement"] >= AGREEMENT_MIN,
          f"float32: token agreement {f32['agreement']:.4f} < "
          f"{AGREEMENT_MIN}")
    check(all(m <= TIE_TOL for m in margins.values()),
          f"bfloat16 divergences off a near-tie: {margins}")


# ---------------------------------------------------------------------------
# cohort sharding across four chips
# ---------------------------------------------------------------------------


def cohort_phase(n_devices):
    import jax
    from repro.configs.base import get_config
    from repro.data.synthetic import mixed_noniid
    from repro.launch.mesh import make_cohort_mesh
    cfg = get_config("lenet-cifar")
    n_clients = 4 * n_devices
    clients = mixed_noniid(n_clients=n_clients,
                           n_per_client=TRAIN["n_per_client"],
                           n_test=TRAIN["n_test"], seed=TRAIN["seed"])
    T = TRAIN["n_per_client"] // TRAIN["batch"]
    log(f"[cohort] lenet-cifar, {n_clients} clients over {n_devices} "
        f"chips (shard_clients, epoch_scan) against one chip")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sharded = run_trainer(cfg, clients, mesh=make_cohort_mesh(n_devices),
                              shard_clients=True, epoch_scan=True)
    ours = [w for w in caught if "/repro/" in w.filename.replace("\\", "/")]
    check(not ours, "fallback warnings: "
          f"{[str(w.message) for w in ours]}")
    tr = sharded["tr"]
    check(tr._shard, "shard_clients fell back to one device")
    rows = set()
    for leaf in jax.tree.leaves(tr.client_params):
        shards = leaf.addressable_shards
        check(len({s.device for s in shards}) == n_devices,
              f"client params on {len(shards)} devices, want {n_devices}")
        rows |= {s.data.shape[0] for s in shards}
    check(rows == {n_clients // n_devices},
          f"client rows per device {sorted(rows)}, want "
          f"{n_clients // n_devices}")
    one = run_trainer(cfg, clients, epoch_scan=True)
    log(f"  sharded {sharded['seconds']:.1f}s, one chip {one['seconds']:.1f}s"
        f"; {n_clients // n_devices} client rows per device")
    compare_runs("shard_clients", sharded, one, T, ce_tol=CE_TOL,
                 exact_rounds=TRAIN["rounds"])


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cohort-sharded training check")
    args = ap.parse_args(argv)

    if SRC.is_dir():
        sys.path.insert(0, str(SRC))
    try:
        from repro.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    log(f"[setup] compilation cache: {enable_compile_cache()}")
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"[setup] jax {jax.__version__}, devices: {device}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {device['platform']})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    failed = []

    def phase(name, fn):
        try:
            return fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)

    if args.chips == 4:
        phase("cohort", lambda: cohort_phase(4))
    else:
        phase("kernels", kernel_phase)
        phase("train", train_phase)
        phase("serve", serve_phase)
    if failed:
        print(f"chip_smoke: failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
