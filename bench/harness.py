"""What every driver gets from the harness: the cell's files, the seed,
the window, spans, and a count of compilations inside the window."""
from __future__ import annotations

import contextlib
import math
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX lowerings, one per new program whether its compiled
    code then comes from the disk cache or not, through
    ``jax.monitoring``."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.lowered = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.LOWER:
            self.lowered += 1


@dataclass
class Ctx:
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    chips: int
    devices: List[Any]
    t_start: float                       # perf_counter at process start
    trace_dir: Optional[Path] = None
    compiles: Optional[CompileCounter] = None
    window_compiles: int = 0
    window_s: float = 0.0

    def sub_seed(self, stream: int) -> int:
        """A 31-bit seed for one purpose, derived from ``--seed``."""
        import numpy as np
        return int(np.random.SeedSequence([self.seed, stream])
                   .generate_state(1)[0] & 0x7FFFFFFF)

    def window_seconds(self) -> float:
        """The measured window's length: ``--seconds``, or in a traced
        run at most the cell's ``trace_seconds``."""
        if self.trace:
            return min(self.seconds, float(self.workload.get(
                "trace_seconds", self.seconds)))
        return self.seconds

    def span(self, what: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench:" + what)

    @contextlib.contextmanager
    def window(self):
        """Wrap the measured window: the profiler in a traced run, and the
        count of programs lowered while it is open."""
        import jax
        before = self.compiles.lowered if self.compiles else 0
        if self.trace:
            self.trace_dir.mkdir(parents=True, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1      # the benchmark's own spans
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        t0 = time.perf_counter()
        try:
            with self.span("window"):
                yield t0
        finally:
            self.window_s = time.perf_counter() - t0
            if self.trace:
                jax.profiler.stop_trace()
            if self.compiles:
                self.window_compiles = self.compiles.lowered - before

    def setup_done(self) -> float:
        return time.perf_counter() - self.t_start


def memory_peak_bytes(devices) -> int:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by nearest rank; an infinite value is
    a request that never finished."""
    v = sorted(values)
    if not v:
        return math.inf
    i = max(0, min(len(v) - 1, int(math.ceil(q / 100.0 * len(v))) - 1))
    return float(v[i])


def check(name: str, value: float, limit: float) -> Dict[str, Any]:
    return {"name": name, "value": float(value), "limit": float(limit),
            "ok": bool(value <= limit)}


def free_device_memory():
    import gc

    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()


def env_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
