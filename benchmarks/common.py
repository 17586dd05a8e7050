"""Shared benchmark scaffolding.

Scale profiles (env REPRO_BENCH_SCALE or --scale):
  smoke — CI-sized: 3 clients, 160 ex/client, 4 rounds (~minutes on CPU)
  std   — 5 clients, 400 ex/client, 12 rounds (default for bench_output)
  paper — the paper's protocol: 5 clients, 1000 ex/client, 20 rounds

Budgets for the C3-Score are the worst-performing method's consumption
on the same run (the paper's §5 convention).

Every table printed through :func:`emit` is also recorded in memory;
:func:`write_bench_json` flushes the records to ``BENCH_<name>.json``
(config + per-row values + host info) so the perf trajectory is
machine-readable across PRs — each benchmark's ``__main__`` writes its
own file, and ``benchmarks.run`` writes one per section plus the
``BENCH_all.json`` aggregate.
"""
from __future__ import annotations

import csv
import dataclasses
import io
import json
import os
import platform
import sys
import time
from dataclasses import dataclass

from repro.configs.base import get_config
from repro.data.synthetic import mixed_cifar, mixed_noniid
from repro.utils.compile_cache import enable_compile_cache

# every benchmark imports this module before it compiles anything
enable_compile_cache()


@dataclass(frozen=True)
class Scale:
    name: str
    n_clients: int
    n_per_client: int
    n_test: int
    rounds: int

    @property
    def smoke(self) -> bool:
        return self.name == "smoke"


SCALES = {
    "smoke": Scale("smoke", 3, 160, 60, 4),
    "std": Scale("std", 5, 400, 120, 16),
    "paper": Scale("paper", 5, 1000, 200, 20),
}


def scale() -> Scale:
    name = os.environ.get("REPRO_BENCH_SCALE", "std")
    for a in sys.argv[1:]:
        if a.startswith("--scale="):
            name = a.split("=", 1)[1]
    return SCALES[name]


def devices_arg(default: int = 0) -> int:
    """``--devices=N`` CLI override (0 = leave the backend alone)."""
    for a in sys.argv[1:]:
        if a.startswith("--devices="):
            return int(a.split("=", 1)[1])
    return default


def ensure_host_devices(n: int) -> int:
    """Request ``n`` emulated host CPU devices for multi-device rows.

    XLA reads ``--xla_force_host_platform_device_count`` ONCE, when the
    backend initializes — so this only works if no jax computation ran
    yet in this process (benchmark ``__main__``s call it first thing).
    On an accelerator backend it refuses: emulated CPU devices would
    stand in for the real chips.  Returns the device count actually
    available; callers emit their multi-device rows only when it
    matches."""
    import jax
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    if n > 1 and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"--devices={n} emulates CPU devices, but the backend is "
            f"{jax.default_backend()} with {jax.device_count()} "
            "device(s); run without --devices to use them")
    got = jax.device_count()
    if n > 1 and got != n:
        print(f"[devices: wanted {n}, backend has {got} — "
              "was jax already initialized? multi-device rows need "
              f"XLA_FLAGS=--xla_force_host_platform_device_count={n}]")
    return got


def lenet_cfg():
    return get_config("lenet-cifar")


def dataset(protocol: str, sc: Scale, seed: int = 0):
    mk = mixed_noniid if protocol == "noniid" else mixed_cifar
    return mk(sc.n_clients, sc.n_per_client, sc.n_test, seed=seed)


_RECORDS: list = []


def emit(table: str, rows, header):
    """Print a CSV block (captured into bench_output.txt) and record it
    for the machine-readable ``BENCH_<name>.json`` dump."""
    buf = io.StringIO()
    w = csv.writer(buf)
    w.writerow(header)
    for r in rows:
        w.writerow(r)
    print(f"### {table}")
    print(buf.getvalue().rstrip())
    print()
    _RECORDS.append({"table": table, "header": list(header),
                     "rows": [[str(c) for c in r] for r in rows]})


def host_info() -> dict:
    import jax
    return {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "cpu_count": os.cpu_count(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
    }


def write_bench_json(name: str, extra: dict | None = None,
                     out_dir: str = ".") -> str | None:
    """Flush every table emitted since the last flush to
    ``BENCH_<name>.json``.  Returns the path (None when nothing was
    recorded — e.g. a section that crashed before its first emit)."""
    if not _RECORDS:
        return None
    payload = {
        "name": name,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "scale": dataclasses.asdict(scale()),
        "argv": sys.argv,
        "host": host_info(),
        "tables": list(_RECORDS),
    }
    if extra:
        payload.update(extra)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    _RECORDS.clear()
    print(f"[bench json -> {path}]")
    return path


def c3_budgets(results):
    """(B_max, C_max) = worst consumption across methods (paper §5)."""
    bmax = max(r["bandwidth_gb"] for r in results)
    cmax = max(r["client_tflops"] for r in results)
    return max(bmax, 1e-9), max(cmax, 1e-9)


def timed(fn):
    t0 = time.time()
    out = fn()
    return out, time.time() - t0
