"""Run one benchmark cell once.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: its entry in ``BENCHMARK.json``
(configuration, chips, metrics), ``bench/workloads/<cell>.json`` (the
traffic and which job runs it), ``bench/configs/<config>.json`` (the model's
sizes), ``bench/drivers/<driver>.py`` and, in a traced run, one reader
``bench/metrics/<metric>.py`` per per-layer metric.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
with the numbers ``correct`` compared, each beside its limit, under
``checks``, the last key; the same numbers end standard error.

The run refuses (non-zero exit, no result) where JAX finds no TPU, or
fewer chips than the cell asks for, or where the program under test is
not beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from bench.harness import ROOT, CompileCounter, Ctx, env_cache_dir, log  # noqa: E402

BENCH = ROOT / "bench"


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_metrics(man: dict, cell: str, kind: str):
    """The manifest's metrics of one kind that this cell reports: those
    that list it, and those without a list."""
    out = []
    for m in man[kind]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        if "workloads" not in m and kind == "per_layer":
            moved = [e for e in cell_metrics(man, cell, "end_to_end")
                     if e["name"] == m["moves"]]
            if not moved:
                continue
        out.append(m)
    return out


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(man: dict, name: str):
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"bench.run: no workload {name!r} in BENCHMARK.json")
    workload = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    conf = next(c for c in man["configs"] if c["name"] == entry["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    return entry, workload, config


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    man = manifest()
    entry, workload, config = load_cell(man, args.workload)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        log("bench.run: the program under test (src/repro) is not beside "
            "the benchmark")
        return 2
    sys.path.insert(0, str(src))

    import jax
    jax.config.update("jax_compilation_cache_dir", env_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": entry["chips"]}
    if device["platform"] != "tpu":
        log(f"bench.run: no TPU (JAX sees {device['platform']}); the "
            "benchmark never falls back to another device")
        return 3
    if len(devices) < entry["chips"]:
        log(f"bench.run: {args.workload} needs {entry['chips']} chips, JAX "
            f"sees {len(devices)}")
        return 3
    from bench.flops import peaks
    peaks(device["kind"])                 # an unknown chip is an error

    ctx = Ctx(name=args.workload, workload=workload, config=config,
              seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              chips=entry["chips"], devices=devices, t_start=T_START,
              trace_dir=ROOT / "bench_traces" / args.workload,
              compiles=counter)
    if ctx.trace and ctx.trace_dir.exists():
        shutil.rmtree(ctx.trace_dir)
    result = run_cell(ctx, man, device)
    print(json.dumps(result))
    return 0


def run_cell(ctx: Ctx, man: dict, device: dict) -> dict:
    """Drive the cell and shape its result line (no chip check: the
    caller has made it)."""
    driver = importlib.import_module(f"bench.drivers.{ctx.workload['driver']}")
    out = driver.run(ctx)
    log(f"[bench] window {ctx.window_s:.6f}s, programs lowered inside it: "
        f"{ctx.window_compiles}")
    result = {"correct": all(c["ok"] for c in out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    metrics = {}
    if not ctx.trace:
        for m in cell_metrics(man, ctx.name, "end_to_end"):
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    if ctx.trace:
        from bench import trace as trace_mod
        from bench.flops import peaks
        tr = trace_mod.load(str(ctx.trace_dir))
        run = {"trace": tr, "counts": out["counts"], "config": ctx.config,
               "workload": ctx.workload, "chips": ctx.chips,
               "peaks": peaks(device["kind"]), "window_s": tr.window_s
               or ctx.window_s}
        for m in cell_metrics(man, ctx.name, "per_layer"):
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace_mod.busy_seconds(tr)
        device["window_s"] = run["window_s"]
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_gaps(tr)}
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]}
                        for c in out["checks"]}
    for c in out["checks"]:
        log(f"check {c['name']} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    return result


if __name__ == "__main__":
    sys.exit(main())
