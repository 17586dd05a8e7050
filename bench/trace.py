"""Reduce one profiler trace to the numbers the per-layer metrics read.

A traced run wraps its measured window in ``jax.profiler`` and marks its
own host calls with ``TraceAnnotation`` spans named ``bench:<what>``.
:func:`load` reads the written ``.xplane.pb`` with nothing but JAX and
keeps three things: the device operations of every chip (start, end,
name and the HLO text where the profiler gives it), the device programs
(modules) run, and the host spans.  The functions below turn those into
busy time, kernel time, exposed collective time and idle gaps.  All
times are seconds on the trace's own clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv)",
                        re.IGNORECASE)


@dataclass
class Op:
    start: float
    end: float
    name: str               # short: the HLO instruction's name
    text: str = ""          # the instruction's text, kept for kernels


@dataclass
class Trace:
    ops: Dict[int, List[Op]] = field(default_factory=dict)      # by chip
    modules: Dict[int, List[Op]] = field(default_factory=dict)  # by chip
    spans: List[Op] = field(default_factory=list)    # host, all threads
    window: Optional[Tuple[float, float]] = None

    @property
    def window_s(self) -> float:
        if self.window is None:
            return 0.0
        return self.window[1] - self.window[0]

    def clip(self, ops: List[Op]) -> List[Op]:
        """Operations inside the window, cut at its edges."""
        if self.window is None:
            return list(ops)
        a, b = self.window
        return [Op(max(o.start, a), min(o.end, b), o.name, o.text)
                for o in ops if o.end > a and o.start < b]


KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


def short_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or the newest one under a directory).

    A TPU's op events are named by their HLO instruction's text; the
    text is kept only for kernel calls (``tpu_custom_call``), whose
    operand shapes the kernel metrics read.
    """
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = tr.ops if line.name == OPS_LINE else tr.modules
                out = dest.setdefault(chip, [])
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    name = ev.name
                    text = name if KERNEL_CALL in name else ""
                    if line.name == OPS_LINE:
                        name = short_name(name)
                    out.append(Op(s, s + ev.duration_ns * 1e-9, name, text))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX) or \
                            ev.name.startswith("PjitFunction"):
                        s = ev.start_ns * 1e-9
                        tr.spans.append(Op(s, s + ev.duration_ns * 1e-9,
                                           ev.name))
    win = [s for s in tr.spans if s.name == WINDOW_SPAN]
    if win:
        tr.window = (win[0].start, win[0].end)
    return tr


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def merge(intervals) -> List[Tuple[float, float]]:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted((o.start, o.end) if isinstance(o, Op) else o
                       for o in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(intervals) -> float:
    return sum(b - a for a, b in merge(intervals))


def subtract(a_ivs, b_ivs) -> float:
    """Length of the union of ``a_ivs`` not covered by the union of
    ``b_ivs``."""
    a, b = merge(a_ivs), merge(b_ivs)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while cur < e:
            if k >= len(b) or b[k][0] >= e:
                total += e - cur
                break
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
    return total


# ---------------------------------------------------------------------------
# reductions the metrics read
# ---------------------------------------------------------------------------


def busy_seconds(tr: Trace) -> float:
    """Seconds in which some operation ran on a chip, inside the window,
    averaged over the chips that ran anything."""
    per = [measure(tr.clip(ops)) for ops in tr.ops.values() if ops]
    return sum(per) / len(per) if per else 0.0


def kernel_ops(tr: Trace, kernel: str) -> List[Op]:
    """Every device call of the named Pallas kernel inside the window, on
    all chips.  XLA names a kernel's call after the kernel and the
    transforms around it (``jvp_panel_gemm_.32``,
    ``vmap_jvp_panel_gemm__.6``, ``masked_adam.122``); another kernel
    whose name merely starts the same (``panel_gemm_bias_relu``) is not
    it."""
    pat = re.compile(rf"(^|_){re.escape(kernel)}_*(\.\d+)?$")
    return [o for ops in tr.ops.values() for o in tr.clip(ops)
            if o.text and pat.search(o.name)]


def module_ops(tr: Trace, fragment: str) -> List[Op]:
    """Device program executions whose name holds ``fragment``."""
    return [o for mods in tr.modules.values() for o in tr.clip(mods)
            if fragment in o.name]


def exposed_collective_seconds(tr: Trace) -> Tuple[float, float]:
    """(collective seconds, of which exposed), averaged over chips: the
    part of the collectives' union that no other operation overlaps on
    the same chip."""
    tot = exp = 0.0
    n = 0
    for ops in tr.ops.values():
        ops = tr.clip(ops)
        coll = [o for o in ops if COLLECTIVE.match(o.name)]
        if not ops:
            continue
        rest = [o for o in ops if not COLLECTIVE.match(o.name)]
        n += 1
        tot += measure(coll)
        exp += subtract(coll, rest)
    return (tot / n, exp / n) if n else (0.0, 0.0)


def _label(tr: Trace, t: float) -> str:
    """What the host was doing at time ``t``: the innermost ``bench:``
    span, and the innermost jitted call inside it."""
    bench = [s for s in tr.spans if s.name.startswith(SPAN_PREFIX)
             and s.name != WINDOW_SPAN and s.start <= t < s.end]
    pjit = [s for s in tr.spans if s.name.startswith("PjitFunction")
            and s.start <= t < s.end]
    out = min(bench, key=lambda s: s.end - s.start).name[len(SPAN_PREFIX):] \
        if bench else "outside bench spans"
    if pjit:
        out += "/" + min(pjit, key=lambda s: s.end - s.start).name
    return out


def idle_gaps(tr: Trace, top: int = 10) -> List[List]:
    """Idle time inside the window by what the host was doing, on the
    first chip: [[label, seconds], ...], most first."""
    if not tr.ops or tr.window is None:
        return []
    chip = min(tr.ops)
    busy = merge(tr.clip(tr.ops[chip]))
    a, b = tr.window
    gaps, cur = [], a
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < b:
        gaps.append((cur, b))
    by: Dict[str, float] = {}
    for s, e in gaps:
        lab = _label(tr, (s + e) / 2)
        by[lab] = by.get(lab, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])][:top]


CONTAINERS = ("while", "conditional", "call")


def top_ops(tr: Trace, top: int = 10) -> List[List]:
    """Device time by operation inside the window, summed over chips and
    divided by their number: [[name, seconds], ...].  Loops and calls,
    whose time is their body's, are left out."""
    by: Dict[str, float] = {}
    n = max(len([o for o in tr.ops.values() if o]), 1)
    for ops in tr.ops.values():
        for o in tr.clip(ops):
            if o.name.split(".")[0] in CONTAINERS:
                continue
            by[o.name] = by.get(o.name, 0.0) + (o.end - o.start) / n
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])][:top]
