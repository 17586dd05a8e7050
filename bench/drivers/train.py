"""Split training: ``AdaSplitTrainer.train()`` called again and again.

One call is one protocol run of ``rounds`` rounds, the first
``round(kappa * rounds)`` local (every client trains its tower alone) and
the rest global (UCB picks ``round(eta * C)`` clients per iteration and
the server trains on their activations), then one evaluation.  Set-up
builds one trainer from the seed, hands it the benchmark's weights,
drives it through its first ``check_steps`` calls (which compile every
program the window uses) and keeps what those calls did.  The window
repeats the same call.  After it, the plain reference replays the first
calls and decides ``correct``.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

from bench import flops
from bench.harness import Ctx, check, free_device_memory, log, \
    memory_peak_bytes
from bench.reference import lenet as ref


def program_config(cfgj: dict):
    """The program's registered configuration with the file's sizes."""
    from repro.configs.base import get_config
    cfg = dataclasses.replace(
        get_config(cfgj["model"]), image_size=cfgj["image_size"],
        n_classes=cfgj["n_classes"],
        conv_channels=tuple(cfgj["conv_channels"]),
        d_model=cfgj["d_model"], mu=cfgj["mu"])
    return cfg


def hparams(ctx: Ctx) -> dict:
    hp = dict(ctx.config["hparams"])
    hp.update(ctx.workload.get("hparams", {}))
    return hp


def _inject(tr, weights):
    """Give the trainer the benchmark's weights, on the placement the
    trainer chose for its own, after checking they have its layout."""
    import jax
    for attr, key in (("client_params", "client"), ("proj_params", "proj"),
                      ("server_params", "server"), ("masks", "masks")):
        theirs, mine = getattr(tr, attr), weights[key]
        if jax.tree.structure(theirs) != jax.tree.structure(mine):
            raise ValueError(f"{attr}: the trainer's layout "
                             f"{jax.tree.structure(theirs)} is not the "
                             f"benchmark's {jax.tree.structure(mine)}")
        for a, b in zip(jax.tree.leaves(theirs), jax.tree.leaves(mine)):
            if a.shape != b.shape:
                raise ValueError(f"{attr}: shape {a.shape} vs {b.shape}")
        setattr(tr, attr, jax.tree.map(
            lambda m, t: jax.device_put(m.astype(t.dtype), t.sharding),
            mine, theirs))


def _snapshot(tr):
    """The trainables and Adam's second moments, by leaf, as the
    reference names them."""
    nu = tr.c_opt["nu"]
    return {"params": ref.leaves({"client": tr.client_params,
                                  "proj": tr.proj_params,
                                  "server": tr.server_params,
                                  "masks": tr.masks}),
            "nu": ref.leaves({"client": nu["c"], "proj": nu["p"],
                              "server": tr.s_opt["nu"],
                              "masks": tr.m_opt["nu"]})}


def build(ctx: Ctx, raw):
    """The trainer, with the benchmark's weights in it."""
    import jax
    from repro.core.adasplit import AdaSplitHParams, AdaSplitTrainer
    from repro.data.synthetic import ClientData
    hp = hparams(ctx)
    mesh = None
    if ctx.workload.get("shard_clients"):
        from repro.launch.mesh import make_cohort_mesh
        mesh = make_cohort_mesh(ctx.chips)
    clients = [ClientData(x, y, xt, yt, dataset_id=i)
               for i, (x, y, xt, yt) in enumerate(raw)]
    fields = {f.name for f in dataclasses.fields(AdaSplitHParams)}
    tr = AdaSplitTrainer(
        program_config(ctx.config),
        AdaSplitHParams(**{k: v for k, v in hp.items() if k in fields},
                        shard_clients=bool(ctx.workload.get(
                            "shard_clients")),
                        seed=ctx.sub_seed(2)),
        clients, mesh=mesh)
    weights = jax.jit(functools.partial(
        ref.init_weights, ctx.config, len(raw)))(
        jax.random.PRNGKey(ctx.sub_seed(3)))
    _inject(tr, weights)
    return tr


def drive(ctx: Ctx, tr, n_calls: int):
    """``n_calls`` calls of ``train()``, keeping per call the selections
    and server losses of its global iterations (what the trainer hands
    its orchestrator) and a snapshot after the first and the last."""
    hp = hparams(ctx)
    record = []
    orig = tr.orch.ingest_epoch

    def keep(sel_idx, losses, *, state, n_rounds=None):
        if sel_idx is not None:
            record.append((np.asarray(sel_idx), np.asarray(losses)))
        return orig(sel_idx, losses, state=state, n_rounds=n_rounds)

    tr.orch.ingest_epoch = keep
    steps, snaps = [], {}
    try:
        for s in range(1, n_calls + 1):
            with ctx.span("train"):
                tr.train(eval_every=hp["rounds"])
            its = []
            for idx, ces in record:
                for r in range(idx.shape[0]):
                    its += [(idx[r, t], ces[r, t])
                            for t in range(idx.shape[1])]
            record.clear()
            steps.append(its)
            if s in (1, n_calls):
                snaps[s] = _snapshot(tr)
    finally:
        del tr.orch.ingest_epoch
    return steps, snaps


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def compare(cand_steps, cand_snaps, ref_steps, ref_snaps, init, grads,
            replay, k, n_calls):
    """The numbers ``correct`` compares (see PERF.md):

    loss_first  worst relative gap, over the selected clients, of the
            server CE of the first global iteration (the first server
            step, on towers trained through the local rounds);
    loss1   relative gap of the mean server CE over call 1's global
            iterations (``loss``: the worst over the calls), logged;
    select  worst shortfall, over every global iteration, of a chosen
            client's score below the k-th best score, both as the
            bandit should have ranked the clients from the selections
            and losses the run itself reported (``replay``), relative to
            that k-th best;
    grad1   median leaf's gap between the candidate's and the
            reference's gradient as Adam recorded it over call 1 (the
            norm of the square root of its second moment), over the
            larger of that leaf's and the median leaf's reference norm;
    change1 worst leaf's gap between the norms of the change after
            call 1, measured the same way;
    changeN the same after the last checked call.

    Leaves whose first reference gradient is under a thousandth of the
    median leaf's move by round-off alone and are left out of the last
    three.
    """
    out, detail = {}, {}
    gaps = []
    for cs, rs in zip(cand_steps, ref_steps):
        if len(cs) != len(rs) or not cs:
            return None
        c = float(np.mean(np.concatenate([np.atleast_1d(x[1]) for x in cs])))
        r = float(np.mean(np.concatenate([np.atleast_1d(x[1]) for x in rs])))
        gaps.append(abs(c - r) / abs(r))
    out["loss1"], out["loss"] = gaps[0], max(gaps)
    (_, c0), (_, r0) = cand_steps[0][0], ref_steps[0][0][:2]
    c0, r0 = np.atleast_1d(c0), np.atleast_1d(r0)
    out["loss_first"] = float(np.max(np.abs(c0 - r0) / np.abs(r0)))
    short = 0.0
    for cs, scores in zip(cand_steps, replay):
        for (idx, _), sc in zip(cs, scores):
            thr = np.sort(sc)[-k]
            short = max(short, max(0.0, thr - float(np.min(sc[idx])))
                        / abs(thr))
    out["select"] = short
    med_g = float(np.median(list(grads.values())))
    kept = [n for n, g in grads.items() if g >= 1e-3 * med_g]

    def leaf_gaps(cand, refv, key):
        med = float(np.median([refv[n] for n in kept]))
        gap = {n: abs(cand[n] - refv[n]) / max(refv[n], med) for n in kept}
        top = sorted(gap, key=gap.get)[::-1][:3]
        detail[key] = {"median_leaf": float(np.median(list(gap.values()))),
                       "worst": [[n, gap[n]] for n in top]}
        return detail[key]

    rms = lambda snap: {n: float(np.sqrt(np.sum(snap["nu"][n])))
                        for n in kept}
    out["grad1"] = leaf_gaps(rms(cand_snaps[1]), rms(ref_snaps[1]),
                             "grad1")["median_leaf"]
    for s, key in ((1, "change1"), (n_calls, f"change{n_calls}")):
        moved = lambda snap: {n: float(np.linalg.norm(snap["params"][n]
                                                      - init[n]))
                              for n in kept}
        out[key] = leaf_gaps(moved(cand_snaps[s]), moved(ref_snaps[s]),
                             key)["worst"][0][1]
    out["excluded_leaves"] = sorted(set(grads) - set(kept))
    out["detail"] = detail
    return out


def reference_numbers(ctx: Ctx, raw, cand_steps, cand_snaps, n_calls):
    """Replay the first calls in the plain reference, following the
    candidate's selections, and compare."""
    import jax
    hp = hparams(ctx)
    weights = jax.jit(functools.partial(
        ref.init_weights, ctx.config, len(raw)))(
        jax.random.PRNGKey(ctx.sub_seed(3)))
    init = ref.leaves(weights)
    proto = ref.Protocol(ctx.config, hp, weights,
                         jax.random.PRNGKey(ctx.sub_seed(2)))
    data = [(x, y) for x, y, _, _ in raw]
    rng = np.random.default_rng(ctx.sub_seed(2))
    ref_steps, ref_snaps = ref.run_protocol(
        proto, data, hp, rng, n_calls, forced=cand_steps,
        snapshot_at=(1, n_calls))
    replay = ref.ucb_replay(len(raw), hp, jax.random.PRNGKey(ctx.sub_seed(2)),
                            cand_steps)
    return compare(cand_steps, cand_snaps, ref_steps, ref_snaps, init,
                   proto.first_grads, replay, proto.k, n_calls)


def reference_candidate(ctx: Ctx, raw, n_calls, *, dtype="float32",
                        fault=None, precision=None):
    """The reference itself in the program's place (the control, or a
    planted fault): its own selections, losses and trainables."""
    import jax
    hp = hparams(ctx)
    weights = jax.jit(functools.partial(
        ref.init_weights, ctx.config, len(raw)))(
        jax.random.PRNGKey(ctx.sub_seed(3)))
    proto = ref.Protocol(ctx.config, hp, weights,
                         jax.random.PRNGKey(ctx.sub_seed(2)), dtype=dtype,
                         fault=fault, precision=precision)
    data = [(x, y) for x, y, _, _ in raw]
    rng = np.random.default_rng(ctx.sub_seed(2))
    steps, snaps = ref.run_protocol(proto, data, hp, rng, n_calls,
                                    snapshot_at=(1, n_calls))
    return [[(i, c) for i, c, _ in its] for its in steps], snaps


def population(ctx: Ctx):
    from bench import traffic
    w = ctx.workload
    return traffic.mixed_noniid(w["clients"], w["examples_per_client"],
                                w["test_per_client"], ctx.sub_seed(1),
                                image_size=ctx.config["image_size"],
                                n_classes=ctx.config["n_classes"])


def run(ctx: Ctx) -> dict:
    w, hp = ctx.workload, hparams(ctx)
    n_calls = int(w["check_steps"])
    t0 = time.perf_counter()
    raw = population(ctx)
    C, B, R = w["clients"], hp["batch_size"], hp["rounds"]
    T = min(len(r[0]) for r in raw) // B
    k = max(1, int(round(hp["eta"] * C)))
    local = int(round(hp["kappa"] * R))
    t1 = time.perf_counter()
    tr = build(ctx, raw)
    t2 = time.perf_counter()
    cand_steps, cand_snaps = drive(ctx, tr, n_calls)
    setup_s = ctx.setup_done()
    log(f"[train] {C} clients x {w['examples_per_client']} examples, "
        f"batch {B}, T={T}, {R} rounds ({local} local), k={k}; set-up "
        f"{setup_s:.3f}s: data {t1 - t0:.3f}s, trainer {t2 - t1:.3f}s, "
        f"{n_calls} checked calls {time.perf_counter() - t2:.3f}s")

    calls = 0
    window = ctx.window_seconds()
    with ctx.window() as t0:
        while True:
            with ctx.span("train"):
                tr.train(eval_every=R)
            calls += 1
            if time.perf_counter() - t0 >= window:
                break
    examples = calls * R * C * T * B
    call_flops = sum(flops.lenet_round_flops(
        ctx.config, clients=C, iters=T, batch=B, selected=k,
        global_phase=r >= local) for r in range(R))
    counts = {"calls": calls, "examples": examples,
              "train_flops": calls * call_flops}
    mem = memory_peak_bytes(ctx.devices[:ctx.chips])
    del tr
    free_device_memory()

    t_ref = time.perf_counter()
    nums = reference_numbers(ctx, raw, cand_steps, cand_snaps, n_calls)
    log(f"[train] reference replay {time.perf_counter() - t_ref:.3f}s")
    limits = w["limits"]
    checks = []
    if nums is None:
        checks.append(check("global_iterations_match", 1.0, 0.0))
    else:
        log(f"[train] leaves left out of the change (reference gradient "
            f"under 1e-3 of the median leaf's): {nums['excluded_leaves']}")
        log(f"[train] numbers: " + ", ".join(
            f"{k} {v!r}" for k, v in nums.items()
            if k not in ("detail", "excluded_leaves")))
        for key, d in nums["detail"].items():
            log(f"[train] {key}: median leaf {d['median_leaf']:.5f}, worst "
                + ", ".join(f"{n} {g:.5f}" for n, g in d["worst"]))
        for key in limits:
            checks.append(check(key, nums[key], limits[key]))
    return {
        "e2e": {"train_examples_per_s": examples / ctx.window_s,
                "setup_s": setup_s},
        "attempted": calls, "failed": 0, "checks": checks,
        "counts": counts, "memory_peak_bytes": mem,
    }
