"""What the control and the planted faults read, at a cell's own size.

    python3 -m bench.control --workload <cell> --seeds 1 2 3 [--seconds s]

Prints JSON lines per seed.  Training: the plain reference in the
program's place one precision lower (bfloat16: the control), with half
of every batch left out (a fault), and at the program's own matmul
precision (one bfloat16 pass: a witness for its rounding); then the
program itself with a fault planted (its state left unchanged; half of
each batch left out), each read against the float32 reference exactly
as a run's ``correct`` reads the program.  Serving: one
short run of the program at the cell's load, then on its checked sample
what the program, the float8 control and a token altered where it is made
read.  These readings, beside the program's over a dozen seeds, set the
limits in ``bench/workloads/<cell>.json`` (see PERF.md).  The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from bench.harness import ROOT, CompileCounter, Ctx, env_cache_dir, log  # noqa: E402
from bench.run import load_cell, manifest  # noqa: E402


@contextlib.contextmanager
def frozen_state():
    """Planted in the program: every epoch dispatch hands back the state
    it was given (its outputs, selections and losses, are still made)."""
    import jax
    from repro.core.adasplit import AdaSplitTrainer
    orig = AdaSplitTrainer._epoch_fn

    def frozen(self, R, T, global_phase):
        fn = orig(self, R, T, global_phase)
        return jax.jit(lambda carry, *a: (carry, fn(carry, *a)[1]))

    AdaSplitTrainer._epoch_fn = frozen
    try:
        yield
    finally:
        AdaSplitTrainer._epoch_fn = orig


@contextlib.contextmanager
def half_batch():
    """Planted in the program's feed: the second half of every staged
    batch is a copy of the first, so every loss is the mean over half of
    the batch, the rest left out."""
    from repro.core.adasplit import AdaSplitTrainer
    orig = AdaSplitTrainer._stage_round_np

    def half(iters, T, n):
        xs, ys = orig(iters, T, n)
        h = xs.shape[2] // 2
        xs[:, :, h:2 * h], ys[:, :, h:2 * h] = xs[:, :, :h], ys[:, :, :h]
        return xs, ys

    AdaSplitTrainer._stage_round_np = staticmethod(half)
    try:
        yield
    finally:
        AdaSplitTrainer._stage_round_np = staticmethod(orig)


def program_readings(ctx, raw, n_calls, plant):
    """The program with a planted fault, driven through its first calls
    as a run's set-up drives it, read against the reference."""
    from bench.drivers import train
    from bench.harness import free_device_memory
    with plant():
        tr = train.build(ctx, raw)
        steps, snaps = train.drive(ctx, tr, n_calls)
    del tr
    free_device_memory()
    return train.reference_numbers(ctx, raw, steps, snaps, n_calls)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--only", nargs="*", default=None,
                    help="training: read only these (control_bf16, "
                    "half_batch_reference, witness_default_precision, "
                    "frozen_state, half_batch)")
    args = ap.parse_args(argv)
    man = manifest()
    entry, workload, config = load_cell(man, args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir", env_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        log("bench.control: no TPU")
        return 3
    for seed in args.seeds:
        ctx = Ctx(name=args.workload, workload=workload, config=config,
                  seed=seed, seconds=args.seconds, trace=False,
                  chips=entry["chips"], devices=jax.devices(),
                  t_start=time.perf_counter(), compiles=CompileCounter())
        out = {"seed": seed}
        if workload["driver"] == "train":
            from bench.drivers import train
            raw = train.population(ctx)
            n = workload["check_steps"]
            for tag, kw in (("control_bf16", {"dtype": "bfloat16"}),
                            ("half_batch_reference", {"fault": "half"}),
                            ("witness_default_precision",
                             {"precision": "default"}),
                            ("frozen_state", frozen_state),
                            ("half_batch", half_batch)):
                if args.only is not None and tag not in args.only:
                    continue
                if callable(kw):
                    nums = program_readings(ctx, raw, n, kw)
                else:
                    steps, snaps = train.reference_candidate(ctx, raw, n,
                                                             **kw)
                    nums = train.reference_numbers(ctx, raw, steps, snaps, n)
                out[tag] = {k: v for k, v in nums.items()
                            if k != "excluded_leaves"}
                print(json.dumps({"seed": seed, tag: out[tag]},
                                 default=float), flush=True)
        else:
            from bench.drivers import serve
            res = serve.run(ctx, readings=out)
            out["metrics"] = res["e2e"]
        print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
